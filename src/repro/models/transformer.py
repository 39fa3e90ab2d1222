"""Decoder-only LM assembly: config, blocks, scan-over-layers, decode.

A single `ModelConfig` expresses all 10 assigned architectures through a
repeating `pattern` of blocks ("mixer:ffn" strings):

  qwen2.5 / granite / qwen3 / llama3 / chameleon : ("attn:mlp",)
  deepseek-v2 / kimi-k2                          : ("attn:moe",) (+k dense)
  jamba          : ("mamba:mlp","mamba:moe","mamba:mlp","attn:moe",
                    "mamba:mlp","mamba:moe","mamba:mlp","mamba:moe")
  xlstm          : ("mlstm:none",)*7 + ("slstm:none",)

Layers are scanned (weights stacked on a leading "layers" axis) so HLO size
and compile time are O(1) in depth; `remat` selects the rematerialization
policy for the scan body.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.attention import AttentionSpec
from repro.attention.state import keep_live
from repro.models import layers as L
from repro.sharding.rules import maybe_constraint
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import xlstm as X
from repro.models.param import Builder

__all__ = ["ModelConfig", "init_lm", "forward_lm", "lm_loss",
           "init_lm_decode_state", "lm_decode_step", "lm_prefill"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 64
    d_ff: int = 2048
    pattern: Tuple[str, ...] = ("attn:mlp",)
    first_k_dense: int = 0          # leading dense (non-MoE) blocks, unrolled
    # attention — one typed operator spec (see repro.attention); the legacy
    # attn_backend/attn_impl string pair is accepted as a deprecation shim
    attn: AttentionSpec = AttentionSpec()
    attn_backend: dataclasses.InitVar[Optional[str]] = None
    attn_impl: dataclasses.InitVar[Optional[str]] = None
    chunk_size: int = 128           # scan chunk (attention inherits; ssm too)
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4         # 0 disables rope
    # MLA (deepseek-v2)
    use_mla: bool = False
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    # MLP / MoE
    mlp_act: str = "swiglu"
    n_experts: int = 0
    moe_top_k: int = 2
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # ssm
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 1500
    cross_attention: bool = False
    pos_emb: str = "none"           # none | sinusoidal (frontends w/o rope)
    # norm / numerics
    norm_type: str = "rmsnorm"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    input_embeddings_only: bool = False  # encoder towers (no vocab/unembed)
    param_dtype: str = "float32"
    activ_dtype: str = "float32"
    remat: str = "full"             # none | dots | full
    logits_softcap: float = 0.0

    def __post_init__(self, attn_backend, attn_impl):
        if attn_backend or attn_impl:
            warnings.warn(
                "ModelConfig(attn_backend=..., attn_impl=...) is deprecated;"
                " pass attn=AttentionSpec(...) instead",
                DeprecationWarning, stacklevel=3)
            object.__setattr__(
                self, "attn", self.attn.with_flags(backend=attn_backend,
                                                   impl=attn_impl))

    @property
    def attn_spec(self) -> AttentionSpec:
        """The attention spec with config-level defaults (chunk_size)
        resolved — what the layers hand to `repro.attention.attention`."""
        if self.attn.chunk_size is not None:
            return self.attn
        return dataclasses.replace(self.attn, chunk_size=self.chunk_size)

    @property
    def n_groups(self) -> int:
        assert self.n_layers_scanned % len(self.pattern) == 0, (
            self.n_layers_scanned, self.pattern)
        return self.n_layers_scanned // len(self.pattern)

    @property
    def n_layers_scanned(self) -> int:
        return self.n_layers - self.first_k_dense

    def dtype(self):
        return jnp.dtype(self.param_dtype)

    def adtype(self):
        return jnp.dtype(self.activ_dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _init_block(b: Builder, kind: str, cfg: ModelConfig,
                force_mlp: bool = False) -> None:
    mixer, ffn = kind.split(":")
    if force_mlp and ffn == "moe":
        ffn = "mlp"
    L.init_norm(b, "norm1", cfg.d_model, cfg.norm_type)
    if mixer == "attn":
        L.init_attention(b, "mixer", cfg)
        if cfg.cross_attention:
            L.init_norm(b, "norm_x", cfg.d_model, cfg.norm_type)
            L.init_attention(b, "cross", cfg)
    elif mixer == "mamba":
        M.init_mamba(b, "mixer", cfg)
    elif mixer == "mlstm":
        X.init_mlstm(b, "mixer", cfg)
    elif mixer == "slstm":
        X.init_slstm(b, "mixer", cfg)
    else:
        raise ValueError(mixer)
    if ffn == "mlp":
        L.init_norm(b, "norm2", cfg.d_model, cfg.norm_type)
        L.init_mlp(b, "ffn", cfg.d_model, cfg.d_ff, cfg.mlp_act)
    elif ffn == "moe":
        L.init_norm(b, "norm2", cfg.d_model, cfg.norm_type)
        MOE.init_moe(b, "ffn", cfg)
    elif ffn != "none":
        raise ValueError(ffn)


def _apply_block(params, x, kind: str, cfg: ModelConfig, *, causal=True,
                 kv_mask=None, enc_out=None, force_mlp=False):
    mixer, ffn = kind.split(":")
    if force_mlp and ffn == "moe":
        ffn = "mlp"
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(params["norm1"], x, norm_type=cfg.norm_type,
                     eps=cfg.norm_eps)
    if mixer == "attn":
        y = L.apply_attention(params["mixer"], h, cfg, causal=causal,
                              kv_mask=kv_mask)
    elif mixer == "mamba":
        y = M.apply_mamba(params["mixer"], h, cfg)
    elif mixer == "mlstm":
        y = X.apply_mlstm(params["mixer"], h, cfg)
    elif mixer == "slstm":
        y = X.apply_slstm(params["mixer"], h, cfg)
    x = x + y
    if mixer == "attn" and cfg.cross_attention and enc_out is not None:
        h = L.apply_norm(params["norm_x"], x, norm_type=cfg.norm_type,
                         eps=cfg.norm_eps)
        x = x + L.apply_attention(params["cross"], h, cfg, causal=False,
                                  kv_x=enc_out)
    if ffn == "mlp":
        h = L.apply_norm(params["norm2"], x, norm_type=cfg.norm_type,
                         eps=cfg.norm_eps)
        x = x + L.apply_mlp(params["ffn"], h, act=cfg.mlp_act)
    elif ffn == "moe":
        h = L.apply_norm(params["norm2"], x, norm_type=cfg.norm_type,
                         eps=cfg.norm_eps)
        y, aux = MOE.apply_moe(params["ffn"], h, cfg)
        x = x + y
    return x, aux


# ---------------------------------------------------------------------------
# LM init / forward
# ---------------------------------------------------------------------------


def init_lm(key: jax.Array, cfg: ModelConfig, *, abstract: bool = False):
    """Returns (params, logical_axes). abstract=True -> ShapeDtypeStructs."""
    b = Builder(key, cfg.dtype(), abstract=abstract)
    if not cfg.input_embeddings_only:
        b.add("embed", (cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
              scale=1.0)
    for i in range(cfg.first_k_dense):
        _init_block(b.sub(f"dense_{i}"), cfg.pattern[0], cfg, force_mlp=True)
    for i, kind in enumerate(cfg.pattern):
        b.stacked(f"blocks_{i}", cfg.n_groups,
                  lambda pb, kind=kind: _init_block(pb, kind, cfg))
    L.init_norm(b, "final_norm", cfg.d_model, cfg.norm_type)
    if not cfg.tie_embeddings and not cfg.input_embeddings_only:
        b.add("unembed", (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return b.params, b.axes


def _sinusoidal(n: int, d: int, dtype) -> jnp.ndarray:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe.astype(dtype)


def _logits(params, x, cfg):
    with jax.named_scope("logits"):
        if cfg.tie_embeddings:
            # tied head: scale by 1/sqrt(d) (embeddings are unit-scale at
            # init)
            logits = jnp.einsum("bnd,vd->bnv", x, params["embed"]) \
                * (cfg.d_model ** -0.5)
        else:
            logits = jnp.einsum("bnd,dv->bnv", x, params["unembed"])
        if cfg.logits_softcap > 0:
            c = cfg.logits_softcap
            logits = c * jnp.tanh(logits / c)
        if logits.ndim == 3:
            logits = maybe_constraint(logits, ("pod", "data"), None, "model")
    return logits


def forward_lm(params, tokens, cfg: ModelConfig, *, causal=True,
               kv_mask=None, embeddings=None, enc_out=None,
               return_hidden=False):
    """tokens: [B, N] int32 (or `embeddings` [B, N, d] for stub frontends)."""
    if embeddings is not None:
        x = embeddings.astype(cfg.adtype())
    else:
        x = params["embed"][tokens].astype(cfg.adtype())
    if cfg.pos_emb == "sinusoidal":
        x = x + _sinusoidal(x.shape[1], cfg.d_model, x.dtype)[None]
    # keep activations batch-sharded (DP) and SEQUENCE-sharded over the
    # tensor axis between blocks (Megatron-SP): the scan-over-layers saved
    # residuals shrink by the TP degree; attention/MLP gather internally.
    # Also stops the FSDP (embed->data) weight sharding from propagating
    # into activations and replicating the batch.
    x = maybe_constraint(x, ("pod", "data"), "model", None)

    aux_total = jnp.zeros((), jnp.float32)
    for i in range(cfg.first_k_dense):
        x, aux = _apply_block(params[f"dense_{i}"], x, cfg.pattern[0], cfg,
                              causal=causal, kv_mask=kv_mask,
                              enc_out=enc_out, force_mlp=True)
        aux_total = aux_total + aux

    def group_body(carry, group_params):
        x, aux_sum = carry
        x = maybe_constraint(x, ("pod", "data"), "model", None)
        aux_g = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.pattern):
            x, aux = _apply_block(group_params[f"blocks_{i}"], x, kind, cfg,
                                  causal=causal, kv_mask=kv_mask,
                                  enc_out=enc_out)
            aux_g = aux_g + aux
        return (x, aux_sum + aux_g), None

    if cfg.remat == "full":
        group_body = jax.checkpoint(group_body,
                                    policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        group_body = jax.checkpoint(
            group_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    stacked = {f"blocks_{i}": params[f"blocks_{i}"]
               for i in range(len(cfg.pattern))}
    (x, aux_total), _ = jax.lax.scan(group_body, (x, aux_total), stacked)

    x = L.apply_norm(params["final_norm"], x, norm_type=cfg.norm_type,
                     eps=cfg.norm_eps)
    if return_hidden:
        return x, aux_total
    return _logits(params, x, cfg), aux_total


def lm_loss(params, batch, cfg: ModelConfig):
    """Next-token cross-entropy. batch: {tokens, (targets|shift), loss_mask?}"""
    tokens = batch["tokens"]
    targets = batch.get("targets")
    if targets is None:
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    logits, aux = forward_lm(params, tokens, cfg,
                             embeddings=batch.get("embeddings"),
                             enc_out=batch.get("enc_out"))
    with jax.named_scope("logits"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
        nll = logz - gold
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
        mask = mask.at[:, -1].set(0.0)
    nll = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return nll + aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Decode (serving): per-layer state, scanned over groups
# ---------------------------------------------------------------------------


def _init_block_state(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                      dtype):
    mixer = kind.split(":")[0]
    if mixer == "attn":
        return L.init_attn_state(cfg, batch, max_len, dtype)
    if mixer == "mamba":
        return M.init_mamba_state(cfg, batch, dtype)
    if mixer == "mlstm":
        return X.init_mlstm_state(cfg, batch)
    if mixer == "slstm":
        return X.init_slstm_state(cfg, batch, dtype)
    raise ValueError(mixer)


def init_lm_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    dtype = cfg.adtype()
    state = {}
    for i in range(cfg.first_k_dense):
        state[f"dense_{i}"] = _init_block_state(cfg.pattern[0], cfg, batch,
                                                max_len, dtype)
    for i, kind in enumerate(cfg.pattern):
        one = _init_block_state(kind, cfg, batch, max_len, dtype)
        state[f"blocks_{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None], (cfg.n_groups,) + x.shape).copy(), one)
    return state


def _decode_block(params, x_t, st, kind, cfg, *, position, enc_out=None,
                  live=None):
    mixer, ffn = kind.split(":")
    h = L.apply_norm(params["norm1"], x_t, norm_type=cfg.norm_type,
                     eps=cfg.norm_eps)
    if mixer == "attn":
        y, st = L.attention_decode(params["mixer"], h, st, cfg,
                                   position=position, live=live)
    else:
        decode = {"mamba": M.mamba_decode, "mlstm": X.mlstm_decode,
                  "slstm": X.slstm_decode}[mixer]
        y, new = decode(params["mixer"], h, st, cfg)
        st = keep_live(live, new, st)
    x_t = x_t + y
    if mixer == "attn" and cfg.cross_attention and enc_out is not None:
        h = L.apply_norm(params["norm_x"], x_t, norm_type=cfg.norm_type,
                         eps=cfg.norm_eps)
        x_t = x_t + L.apply_attention(params["cross"], h, cfg, causal=False,
                                      kv_x=enc_out)
    if ffn in ("mlp", "moe"):
        h = L.apply_norm(params["norm2"], x_t, norm_type=cfg.norm_type,
                         eps=cfg.norm_eps)
        if ffn == "mlp" or "router" not in params.get("ffn", {}):
            x_t = x_t + L.apply_mlp(params["ffn"], h, act=cfg.mlp_act)
        else:
            y, _ = MOE.apply_moe(params["ffn"], h, cfg, full_capacity=True)
            x_t = x_t + y
    return x_t, st


def lm_decode_step(params, state, token_t, cfg: ModelConfig, *, position,
                   enc_out=None, live=None):
    """One token for the whole model. token_t: [B] int32. Returns
    (logits [B, vocab], new_state).

    `live` [B] bool (slot-pooled serving): a slot that is not live keeps
    its decode state unchanged, layer by layer; None steps every slot.
    The stacked layer states ride the layer scan as a carry, so the whole
    state stays one buffer from input to output (a donated pool is
    updated in place)."""
    x = params["embed"][token_t][:, None].astype(cfg.adtype())
    if cfg.pos_emb == "sinusoidal":
        d = cfg.d_model
        dim = jnp.arange(0, d, 2, dtype=jnp.float32)
        # position: scalar (shared timeline) or [B] (slot-indexed serving)
        pos = jnp.atleast_1d(jnp.asarray(position, jnp.float32))
        ang = pos[:, None] / jnp.power(10000.0, dim / d)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:, None]
        x = x + pe.astype(x.dtype)

    for i in range(cfg.first_k_dense):
        x, st = _decode_block(params[f"dense_{i}"], x, state[f"dense_{i}"],
                              cfg.pattern[0], cfg, position=position,
                              enc_out=enc_out, live=live)
        state = {**state, f"dense_{i}": st}

    def group_body(carry, xs):
        x_t, pool = carry
        group_params, g = xs
        for i, kind in enumerate(cfg.pattern):
            key = f"blocks_{i}"
            st = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, g, keepdims=False),
                pool[key])
            x_t, st = _decode_block(group_params[key], x_t, st, kind, cfg,
                                    position=position, enc_out=enc_out,
                                    live=live)
            pool = {**pool, key: jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, g, 0),
                pool[key], st)}
        return (x_t, pool), None

    stacked_p = {f"blocks_{i}": params[f"blocks_{i}"]
                 for i in range(len(cfg.pattern))}
    stacked_s = {f"blocks_{i}": state[f"blocks_{i}"]
                 for i in range(len(cfg.pattern))}
    (x, new_stacked), _ = jax.lax.scan(
        group_body, (x, stacked_s),
        (stacked_p, jnp.arange(cfg.n_groups, dtype=jnp.int32)))
    state = {**state, **new_stacked}
    x = L.apply_norm(params["final_norm"], x, norm_type=cfg.norm_type,
                     eps=cfg.norm_eps)
    return _logits(params, x, cfg)[:, 0], state


def lm_prefill(params, tokens, cfg: ModelConfig, state, *, enc_out=None,
               offset=None, kv_mask=None):
    """Prefill a prompt through the decode-state machinery.

    For fastmax archs this is the chunked causal scan per layer (linear in
    prompt length); for the softmax baseline it fills the KV cache.

    `offset` (traced scalar) resumes an already-primed state: this call's
    tokens occupy positions [offset, offset + n) — the serving engine's
    chunked-prefill tick (repro.serve). `kv_mask` ([B, N], 1 = real token)
    masks right-padding in a partial final chunk; padding contributes
    nothing to the carried attention state. SSM mixers (mamba/xlstm) resume
    through their own recurrent states but do not support kv_mask — the
    engine only pads chunks for attention-mixer architectures.
    """
    x = params["embed"][tokens].astype(cfg.adtype())
    if cfg.pos_emb == "sinusoidal":
        if offset is None:
            x = x + _sinusoidal(x.shape[1], cfg.d_model, x.dtype)[None]
        else:
            d = cfg.d_model
            dim = jnp.arange(0, d, 2, dtype=jnp.float32)
            pos = (offset + jnp.arange(x.shape[1])).astype(jnp.float32)
            ang = pos[:, None] / jnp.power(10000.0, dim / d)
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
            x = x + pe[None].astype(x.dtype)

    def block_prefill(params_b, x, st, kind):
        mixer, ffn = kind.split(":")
        h = L.apply_norm(params_b["norm1"], x, norm_type=cfg.norm_type,
                         eps=cfg.norm_eps)
        if mixer == "attn":
            y, st = L.attention_prefill(params_b["mixer"], h, st, cfg,
                                        kv_mask=kv_mask, offset=offset)
        elif mixer == "mamba":
            xi, z, delta, a, bm_, cm_, conv = M._pre_ssm(
                params_b["mixer"], h, cfg, conv_state=st.conv)
            yss, hf = M._selective_scan(
                xi.astype(jnp.float32), delta.astype(jnp.float32), a,
                bm_.astype(jnp.float32), cm_.astype(jnp.float32),
                params_b["mixer"]["D"].astype(jnp.float32),
                h0=st.h, chunk=cfg.chunk_size)
            y = jnp.einsum("bnd,de->bne",
                           yss.astype(h.dtype) * jax.nn.silu(z),
                           params_b["mixer"]["out_proj"])
            st = M.MambaState(conv=conv, h=hf)
        elif mixer == "mlstm":
            y, st = X.apply_mlstm_stateful(params_b["mixer"], h, cfg, st)
        elif mixer == "slstm":
            y, st = X.apply_slstm_stateful(params_b["mixer"], h, cfg, st)
        else:
            raise ValueError(mixer)
        x = x + y
        if mixer == "attn" and cfg.cross_attention and enc_out is not None:
            h = L.apply_norm(params_b["norm_x"], x, norm_type=cfg.norm_type,
                             eps=cfg.norm_eps)
            x = x + L.apply_attention(params_b["cross"], h, cfg, causal=False,
                                      kv_x=enc_out)
        if ffn in ("mlp", "moe"):
            h = L.apply_norm(params_b["norm2"], x, norm_type=cfg.norm_type,
                             eps=cfg.norm_eps)
            # first_k_dense blocks carry an MLP even in "moe" patterns
            if ffn == "mlp" or "router" not in params_b["ffn"]:
                x = x + L.apply_mlp(params_b["ffn"], h, act=cfg.mlp_act)
            else:
                y, _ = MOE.apply_moe(params_b["ffn"], h, cfg,
                                     full_capacity=True)
                x = x + y
        return x, st

    for i in range(cfg.first_k_dense):
        x, st = block_prefill(params[f"dense_{i}"], x, state[f"dense_{i}"],
                              cfg.pattern[0])
        state = {**state, f"dense_{i}": st}

    def group_body(x, xs):
        group_params, group_state = xs
        new_states = {}
        for i, kind in enumerate(cfg.pattern):
            x, st = block_prefill(group_params[f"blocks_{i}"], x,
                                  group_state[f"blocks_{i}"], kind)
            new_states[f"blocks_{i}"] = st
        return x, new_states

    stacked_p = {f"blocks_{i}": params[f"blocks_{i}"]
                 for i in range(len(cfg.pattern))}
    stacked_s = {f"blocks_{i}": state[f"blocks_{i}"]
                 for i in range(len(cfg.pattern))}
    x, new_stacked = jax.lax.scan(group_body, x, (stacked_p, stacked_s))
    state = {**state, **new_stacked}
    x = L.apply_norm(params["final_norm"], x, norm_type=cfg.norm_type,
                     eps=cfg.norm_eps)
    return _logits(params, x, cfg), state
