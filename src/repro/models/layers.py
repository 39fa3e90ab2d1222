"""Model layers: norms, RoPE, MLP, and attention with pluggable score backend.

The attention layer is where the paper's technique plugs in: the model
config's `attn: AttentionSpec` selects the operator (softmax baseline vs
the paper's fastmax p=1/2 polynomial kernels) and every call goes through
the `repro.attention` dispatcher. Everything else (GQA, qk-norm, biases,
RoPE, MLA) is orthogonal — FAST is a drop-in replacement for the score
computation, which is exactly the paper's §5 claim.

Decode states (repro.attention unified protocol):
  softmax  -> KVCache (O(N) per sequence)
  fastmax  -> Moments (O(D^2 Dv) per kv head, independent of context length)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro import attention as A
from repro.attention import AttnState, KVCache  # noqa: F401 (re-export)
from repro.models.param import Builder

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(b: Builder, name: str, dim: int, norm_type: str = "rmsnorm"):
    sub = b.sub(name)
    sub.add("scale", (dim,), ("embed",), init="ones")
    if norm_type == "layernorm":
        sub.add("bias", (dim,), ("embed",), init="zeros")


def apply_norm(params, x, *, norm_type: str = "rmsnorm", eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if norm_type == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    elif norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(
            jnp.float32)
    else:
        raise ValueError(norm_type)
    return out.astype(x.dtype)


def rms_norm_headwise(x, eps: float = 1e-6):
    """Parameter-free per-head RMS norm (qk_norm without learned scale is
    handled by callers passing a scale param)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: [B, H, N, D]; positions: [B, N] or [N]."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # [B,1,N,D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------


def init_mlp(b: Builder, name: str, d_model: int, d_ff: int, act: str):
    sub = b.sub(name)
    if act == "swiglu":
        sub.add("wi_gate", (d_model, d_ff), ("embed", "ff"))
        sub.add("wi_up", (d_model, d_ff), ("embed", "ff"))
    else:
        sub.add("wi", (d_model, d_ff), ("embed", "ff"))
    sub.add("wo", (d_ff, d_model), ("ff", "embed"))


def apply_mlp(params, x, *, act: str):
    if act == "swiglu":
        g = jnp.einsum("bnd,df->bnf", x, params["wi_gate"])
        u = jnp.einsum("bnd,df->bnf", x, params["wi_up"])
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("bnd,df->bnf", x, params["wi"]))
    return jnp.einsum("bnf,fd->bnd", h, params["wo"])


# ---------------------------------------------------------------------------
# Attention (GQA + pluggable backend + optional MLA projections)
# ---------------------------------------------------------------------------
# KVCache / AttnState moved to repro.attention.state (re-exported above).


def init_attention(b: Builder, name: str, cfg) -> None:
    sub = b.sub(name)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.use_mla:
        rank = cfg.kv_lora_rank
        qk_dim = cfg.qk_nope_dim + cfg.qk_rope_dim
        sub.add("wq", (d, hq, qk_dim), ("embed", "heads", "head_dim"))
        sub.add("w_dkv", (d, rank + cfg.qk_rope_dim), ("embed", None))
        sub.add("w_uk", (rank, hq, cfg.qk_nope_dim), (None, "heads", "head_dim"))
        sub.add("w_uv", (rank, hq, hd), (None, "heads", "head_dim"))
        sub.add("wo", (hq, hd, d), ("heads", "head_dim", "embed"),
                fan_in=hq * hd)
    else:
        sub.add("wq", (d, hq, hd), ("embed", "heads", "head_dim"))
        sub.add("wk", (d, hkv, hd), ("embed", "kv_heads", "head_dim"))
        sub.add("wv", (d, hkv, hd), ("embed", "kv_heads", "head_dim"))
        sub.add("wo", (hq, hd, d), ("heads", "head_dim", "embed"),
                fan_in=hq * hd)
        if cfg.qkv_bias:
            sub.add("bq", (hq, hd), ("heads", "head_dim"), init="zeros")
            sub.add("bk", (hkv, hd), ("kv_heads", "head_dim"), init="zeros")
            sub.add("bv", (hkv, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        sub.add("q_norm_scale", (cfg.qk_nope_dim + cfg.qk_rope_dim
                                 if cfg.use_mla else hd,),
                (None,), init="ones")
        sub.add("k_norm_scale", (cfg.qk_nope_dim + cfg.qk_rope_dim
                                 if cfg.use_mla else hd,),
                (None,), init="ones")


def _project_qkv(params, x, cfg, positions):
    """Returns q:[B,Hq,N,Dq], k:[B,Hkv,N,Dq], v:[B,Hkv,N,Dv]."""
    if cfg.use_mla:
        q = jnp.einsum("bnd,dhk->bhnk", x, params["wq"])
        ckv = jnp.einsum("bnd,dr->bnr", x, params["w_dkv"])
        c, k_rope = (ckv[..., : cfg.kv_lora_rank],
                     ckv[..., cfg.kv_lora_rank:])
        k_nope = jnp.einsum("bnr,rhk->bhnk", c, params["w_uk"])
        v = jnp.einsum("bnr,rhk->bhnk", c, params["w_uv"])
        q_nope, q_rope = (q[..., : cfg.qk_nope_dim], q[..., cfg.qk_nope_dim:])
        q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
        k_rope = apply_rope(k_rope[:, None], positions, cfg.rope_theta)
        k_rope = jnp.broadcast_to(
            k_rope, (x.shape[0], q.shape[1], x.shape[1], cfg.qk_rope_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        # MLA decompresses to per-(q)head k/v: treat as Hkv == Hq downstream
    else:
        q = jnp.einsum("bnd,dhk->bhnk", x, params["wq"])
        k = jnp.einsum("bnd,dhk->bhnk", x, params["wk"])
        v = jnp.einsum("bnd,dhk->bhnk", x, params["wv"])
        if cfg.qkv_bias:
            q = q + params["bq"][None, :, None, :]
            k = k + params["bk"][None, :, None, :]
            v = v + params["bv"][None, :, None, :]
        if cfg.rope_theta > 0:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.qk_norm:
        q = rms_norm_headwise(q) * params["q_norm_scale"]
        k = rms_norm_headwise(k) * params["k_norm_scale"]
    return q, k, v


def apply_attention(params, x, cfg, *, causal=True, positions=None,
                    kv_mask=None, kv_x: Optional[jnp.ndarray] = None):
    """Full-sequence attention. `kv_x` (cross-attention source) optional."""
    b, n, _ = x.shape
    if positions is None:
        positions = jnp.arange(n, dtype=jnp.int32)
    if kv_x is None:
        q, k, v = _project_qkv(params, x, cfg, positions)
    else:
        # cross-attention: q from x, k/v from kv_x (no causal, no rope on kv)
        m = kv_x.shape[1]
        kv_pos = jnp.arange(m, dtype=jnp.int32)
        q, _, _ = _project_qkv(params, x, cfg, positions)
        _, k, v = _project_qkv(params, kv_x, cfg, kv_pos)
    # grouped path: moments computed once per KV head (G-fold combine);
    # the head-sharded group reshape tiles cleanly because consecutive
    # q-head shards stay within one kv group (H/s <= G for all configs)
    o = A.attention(q, k, v, cfg.attn_spec, causal=causal, kv_mask=kv_mask)
    return jnp.einsum("bhnk,hkd->bnd", o.astype(x.dtype), params["wo"])


# -- decode (unified repro.attention state protocol) --------------------------


def _kv_dims(cfg):
    """(n_kv_heads, q_head_dim) as the decode state sees them (MLA
    decompresses to per-q-head k/v, so Hkv == Hq there)."""
    hkv = cfg.n_heads if cfg.use_mla else cfg.n_kv_heads
    dq = (cfg.qk_nope_dim + cfg.qk_rope_dim) if cfg.use_mla else cfg.head_dim
    return hkv, dq


def init_attn_state(cfg, batch: int, max_len: int, dtype) -> AttnState:
    hkv, dq = _kv_dims(cfg)
    return A.init_state(cfg.attn_spec, batch=batch, n_kv_heads=hkv,
                        q_head_dim=dq, v_head_dim=cfg.head_dim,
                        max_len=max_len, dtype=dtype)


def attention_decode(params, x_t, state: AttnState, cfg, *, position,
                     live=None):
    """One-token decode. x_t: [B, 1, d]. Returns (y_t, new_state).

    `position` is a scalar (shared timeline — the legacy serve loop) or a
    [B] vector (slot-indexed serving: every sequence sits at its own
    context length, so RoPE must rotate per slot). `live` [B] bool keeps
    the state of the slots that are not live unchanged."""
    pos = jnp.atleast_1d(jnp.asarray(position, jnp.int32))[:, None]
    q, k, v = _project_qkv(params, x_t, cfg, pos)
    o, new = A.step(state, q, k, v, cfg.attn_spec, live=live)
    y = jnp.einsum("bhnk,hkd->bnd", o.astype(x_t.dtype), params["wo"])
    return y, new


def attention_prefill(params, x, state: AttnState, cfg, *, positions=None,
                      kv_mask=None, offset=None):
    """Prefill a prompt, returning outputs and a primed decode state.

    `offset`/`kv_mask` make it a resumable chunk prefill (repro.serve):
    the chunk's tokens occupy positions [offset, offset + n) and padding
    rows (kv_mask 0) contribute nothing to the carried state."""
    b, n, _ = x.shape
    if positions is None:
        off = 0 if offset is None else offset
        positions = off + jnp.arange(n, dtype=jnp.int32)
    q, k, v = _project_qkv(params, x, cfg, positions)
    o, new = A.prefill(q, k, v, cfg.attn_spec, state=state, kv_mask=kv_mask,
                       offset=offset)
    y = jnp.einsum("bhnk,hkd->bnd", o.astype(x.dtype), params["wo"])
    return y, new
