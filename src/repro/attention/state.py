"""Unified decode-state protocol: `init_state` / `prefill` / `step`.

One streaming-inference surface for every backend family:

  softmax  -> `KVCache` (O(N) per sequence, the baseline's cost)
  fastmax  -> `Moments` (O(D^2 Dv) per kv head, INDEPENDENT of context —
              the paper's asymptotic punchline at inference)
  hybrid   -> BOTH legs: the fastmax moments plus a fixed-size rolling
              window `KVCache` of the last W = min(spec.window,
              chunk_size) tokens (the exact near-field band) — still
              O(1) in context length. W=0 carries moments only
              (bitwise fastmax).

`AttnState` is the union carried through the model's scan-over-layers;
at most one of (kv, moments) is populated — except the hybrid family,
which carries both. This protocol subsumes the seed's
`repro.core.decode_state` module and the per-backend decode branches that
lived in `repro.models.layers`.

Backends declaring `decode_kernel` (fastmax-kernel) run prefill and step
through the Pallas kernels on the SAME `Moments` carry: prefill's final
moments are emitted by the forward kernel itself (no recompute pass) and
each step is the fused update+combine decode kernel. Off-TPU the protocol
falls back to the jnp moment step with one logged routing line
(REPRO_DECODE_KERNEL=1 forces the kernel in interpret mode — tests/CI;
=0 disables it everywhere).

Under a multi-device mesh the kernels launch shard_map-wrapped
(`repro.kernels.sharded`) in heads or feature (Dv) mode — since the
Dv-blocked backward landed, that covers TRAINING at every TP degree too
(`attention/backends.py`), so the serve protocol here and the trainable
path commit one and the same moment layout between steps
(`decode_state_shardings`).
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.attention.api import feature_shard_flag
from repro.attention.registry import _log_once, resolve
from repro.attention.spec import AttentionSpec
from repro.core.decode_state import init_fastmax_state
from repro.core.hybrid import _hybrid_scan, effective_window, roll_window
from repro.core.ref import poly_kernel
from repro.core.fastmax import (
    Moments,
    _causal_scan,
    _constrain_moments_j,
    combine_with_queries,
    compute_moments,
    normalize_qk,
)
from repro.core.softmax import softmax_attention

__all__ = ["KVCache", "AttnState", "init_state", "prefill", "step",
           "keep_live", "use_decode_kernel"]


def use_decode_kernel(spec: AttentionSpec) -> bool:
    """True when this spec's decode should run the fused Pallas kernels.

    Requires a backend with the `decode_kernel` capability (fastmax-kernel).
    On TPU that routes decode to the kernel; elsewhere the jnp moment step
    is the fallback (logged once). REPRO_DECODE_KERNEL=1 forces the kernel
    (interpret mode off-TPU); =0 disables it even on TPU.

    Under a multi-device mesh the kernels run shard_map-wrapped
    (`repro.kernels.sharded`): heads mode when kv heads divide the 'model'
    axis, feature (Dv) mode otherwise — the per-call plan is picked in
    `_kernel_plan`; only dims that fit NEITHER mode fall back to the jnp
    feature-TP moment step (logged).
    """
    if spec.family == "softmax":
        return False
    backend = resolve(spec, causal=True)
    if not backend.caps.decode_kernel:
        return False
    env = os.environ.get("REPRO_DECODE_KERNEL", "auto").lower()
    if env in ("0", "off", "never"):
        _log_once(f"decode: {backend.name} kernel disabled "
                  f"(REPRO_DECODE_KERNEL={env})")
        return False
    if env in ("1", "force", "always"):
        _log_once(f"decode: {backend.name} native-state kernel (forced; "
                  f"interpret off-TPU)")
        return True
    if jax.default_backend() == "tpu":
        _log_once(f"decode: {backend.name} native-state kernel")
        return True
    _log_once(
        f"decode: {backend.name} targets tpu; platform="
        f"{jax.default_backend()} -> jnp moment step fallback")
    return False


def _kernel_plan(q, k, v):
    """(mesh, plan) for a kernel launch under the active mesh.

    mesh None -> single-device: plain kernel call. mesh set, plan None ->
    the mesh tensor-parallelizes but neither kv heads nor Dv divide the
    'model' axis: route to the jnp feature-TP moment step (logged by the
    caller). Otherwise the kernel runs shard_map-wrapped per the plan.
    """
    from repro.kernels.sharded import nontrivial_mesh, plan_kernel_sharding

    mesh = nontrivial_mesh()
    if mesh is None:
        return None, None
    plan = plan_kernel_sharding(mesh, batch=q.shape[0], hq=q.shape[1],
                                hkv=k.shape[1], dv=v.shape[-1])
    if plan is not None:
        _log_once(f"decode: fastmax kernel {plan.describe()}")
    return mesh, plan


class KVCache(NamedTuple):
    k: jnp.ndarray       # [B, Hkv, Nmax, D]
    v: jnp.ndarray       # [B, Hkv, Nmax, Dv]
    length: jnp.ndarray  # [] int32 (shared), or [B] int32 (slot-indexed:
    #                      per-sequence write cursors — repro.serve pools)
    mask: jnp.ndarray    # [B, Hkv, Nmax] validity (1=real token) — lets a
    #                      masked prefill stay masked through every step


class AttnState(NamedTuple):
    """Union decode state. softmax uses `kv`, fastmax uses `moments`;
    hybrid uses both (`kv` is the rolling near-field window, W slots)."""
    kv: Optional[KVCache]
    moments: Optional[Moments]


def _window_slots(spec: AttentionSpec) -> int:
    """Rolling-window size the hybrid decode state carries (0 = none)."""
    if spec.family != "hybrid":
        return 0
    return effective_window(spec.window, spec.resolved().chunk_size)


def _check_state(state: AttnState, spec: AttentionSpec) -> None:
    if spec.family == "hybrid":
        if state.moments is None or (_window_slots(spec) > 0
                                     and state.kv is None):
            raise ValueError(
                f"AttnState lacks the moments/window legs required by "
                f"{spec} — the state was initialized for a different "
                f"attention family or window")
        return
    leg = "kv" if spec.family == "softmax" else "moments"
    if getattr(state, leg) is None:
        raise ValueError(
            f"AttnState carries no {leg!r} but spec is {spec} — the state "
            f"was initialized for a different attention family")


def init_state(spec: AttentionSpec, *, batch: int, n_kv_heads: int,
               q_head_dim: int, v_head_dim: int, max_len: int,
               dtype=jnp.float32) -> AttnState:
    """Fresh per-layer decode state for `batch` sequences of <= max_len."""
    backend = resolve(spec, causal=True)
    if not backend.caps.decode:
        raise ValueError(
            f"backend {backend.name!r} has no decode path; use a spec whose "
            f"backend declares decode=True")
    if spec.family == "softmax":
        kv = KVCache(
            k=jnp.zeros((batch, n_kv_heads, max_len, q_head_dim), dtype),
            v=jnp.zeros((batch, n_kv_heads, max_len, v_head_dim), dtype),
            length=jnp.zeros((), jnp.int32),
            mask=jnp.ones((batch, n_kv_heads, max_len), jnp.float32),
        )
        return AttnState(kv=kv, moments=None)
    mom = init_fastmax_state(batch, n_kv_heads, q_head_dim, v_head_dim,
                             p=spec.p, dtype=jnp.float32)
    w = _window_slots(spec)
    if w > 0:
        # hybrid near-field window: the last <=W tokens, right-aligned
        # (row W-1 most recent); `length` counts TOTAL tokens folded so
        # far (moments semantics), not a write cursor — the shift-append
        # is position-independent. mask starts all-zero (window empty).
        kv = KVCache(
            k=jnp.zeros((batch, n_kv_heads, w, q_head_dim), dtype),
            v=jnp.zeros((batch, n_kv_heads, w, v_head_dim), dtype),
            length=jnp.zeros((), jnp.int32),
            mask=jnp.zeros((batch, n_kv_heads, w), jnp.float32),
        )
        return AttnState(kv=kv, moments=mom)
    return AttnState(kv=None, moments=mom)


def prefill(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
            spec: AttentionSpec, *, state: AttnState,
            kv_mask: Optional[jnp.ndarray] = None,
            offset: Optional[jnp.ndarray] = None):
    """Causal prefill of a prompt: returns (outputs, primed AttnState).

    softmax: fills the KV cache. fastmax: one chunked causal scan produces
    BOTH the outputs and the final moments (the seed recomputed moments in a
    second pass).

    `offset` (traced scalar) makes the prefill RESUMABLE: the incoming
    `state` is treated as the state of tokens [0, offset) and this call
    appends tokens [offset, offset + n) — the chunked-prefill primitive of
    the serving engine (`repro.serve`). softmax writes the chunk at
    `offset` in the cache and attends over the valid prefix via `q_offset`;
    fastmax seeds the causal scan with the carried moments. With
    `offset=None` the legacy whole-prompt behavior (and its exact HLO) is
    preserved. `kv_mask` may be [B, N] or [B, Hkv, N]; with a vector
    `length` lane (slot pools) the new lengths are per-sequence
    offset + (valid tokens in this chunk).
    """
    b, n = q.shape[0], q.shape[2]
    hkv = k.shape[1]
    _check_state(state, spec)
    if kv_mask is not None and kv_mask.ndim == 2:
        kv_mask = jnp.broadcast_to(kv_mask[:, None], (b, hkv, n))
    if spec.family == "softmax":
        from repro.sharding.rules import constrain_kv_cache
        kv = state.kv
        off = jnp.asarray(0 if offset is None else offset, jnp.int32)
        kc = jax.lax.dynamic_update_slice_in_dim(
            kv.k, k.astype(kv.k.dtype), off, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(
            kv.v, v.astype(kv.v.dtype), off, axis=2)
        kc = constrain_kv_cache(kc)
        vc = constrain_kv_cache(vc)
        mc = kv.mask
        if kv_mask is not None:
            # persist prompt padding so every later step keeps it masked
            mc = jax.lax.dynamic_update_slice_in_dim(
                mc, kv_mask.astype(mc.dtype), off, axis=2)
        if offset is None:
            o = softmax_attention(q, k, v, causal=True, kv_mask=kv_mask)
        else:
            # resume: attend over the whole cache — rows < offset are the
            # carried prefix (validity from the mask lane), rows >= offset+n
            # are excluded causally via q_offset
            o = softmax_attention(q, kc, vc, causal=True, q_offset=off,
                                  kv_mask=mc)
        if kv.length.ndim == 0:
            # legacy shared cursor: padding rows stay masked via the mask
            # lane but still occupy cache rows (decode appends at n)
            new_len = off + jnp.asarray(n, jnp.int32)
        else:
            # slot pools: per-sequence cursors — decode appends right after
            # each sequence's last VALID token
            nvalid = (jnp.full((b,), n, jnp.int32) if kv_mask is None else
                      jnp.sum(kv_mask[:, 0, :] > 0, axis=-1).astype(jnp.int32))
            new_len = off + jnp.broadcast_to(nvalid, kv.length.shape)
        return o, AttnState(kv=KVCache(kc, vc, new_len, mc), moments=None)
    spec_r = spec.resolved()
    qh = normalize_qk(q) if spec.normalize else q
    kh = normalize_qk(k) if spec.normalize else k
    w_slots = _window_slots(spec)
    if w_slots > 0:
        # hybrid: one jnp scan yields outputs AND the final moments; the
        # near-field window is recompacted to the last <=W valid tokens
        # (normalized keys — band scores are q̂·k̂). With `offset` the
        # carried window seeds the scan's previous-chunk buffer and the
        # carried moments seed the far field. W=0 hybrid falls through to
        # the fastmax moment paths below (bitwise identical).
        fs = feature_shard_flag(hkv)
        kv = state.kv
        if offset is not None:
            _log_once("prefill: hybrid resumable (offset) chunk via the "
                      "jnp hybrid scan")
            init, init_win = state.moments, (kv.k, kv.v, kv.mask)
        else:
            init, init_win = None, None
        o, final = _hybrid_scan(
            qh, kh, v, p=spec.p, window=spec_r.window,
            chunk_size=spec_r.chunk_size, kv_mask=kv_mask,
            denom_eps=spec.denom_eps, feature_shard=fs,
            init=init, init_win=init_win)
        m = (jnp.ones((b, hkv, n), jnp.float32) if kv_mask is None
             else kv_mask.astype(jnp.float32))
        nk, nv, nm = roll_window(
            kv.k if offset is not None else None,
            kv.v if offset is not None else None,
            kv.mask if offset is not None else None,
            kh, v, m, w_slots)
        off = jnp.asarray(0 if offset is None else offset, jnp.int32)
        if kv.length.ndim == 0:
            new_len = off + jnp.asarray(n, jnp.int32)
        else:
            nvalid = (jnp.full((b,), n, jnp.int32) if kv_mask is None else
                      jnp.sum(kv_mask[:, 0, :] > 0,
                              axis=-1).astype(jnp.int32))
            new_len = off + jnp.broadcast_to(nvalid, kv.length.shape)
        nkv = KVCache(nk.astype(kv.k.dtype), nv.astype(kv.v.dtype),
                      new_len, nm)
        return o.astype(q.dtype), AttnState(kv=nkv,
                                            moments=Moments(*final))
    # resumable chunked prefill seeds the scan with the carried moments
    init = None if offset is None else state.moments
    if use_decode_kernel(spec):
        # one kernel launch yields outputs AND the final carry — the
        # prefill→decode handoff without recomputing moments
        from repro.kernels import ops as kernel_ops
        mesh, plan = _kernel_plan(q, k, v)
        if plan is not None and init is None:
            from repro.kernels.sharded import fastmax_prefill_sharded
            o, state = fastmax_prefill_sharded(
                qh, kh, v, p=spec.p, chunk_size=spec_r.chunk_size,
                denom_eps=spec.denom_eps, kv_mask=kv_mask, plan=plan)
            return o.astype(q.dtype), AttnState(kv=None,
                                                moments=Moments(*state))
        if mesh is None:
            _log_once("prefill: fastmax-kernel " + (
                "whole-prompt kernel" if init is None
                else "resumable (offset) chunk, kernel seeded with the "
                     "carried moments"))
            o, state = kernel_ops.fastmax_prefill_kernel(
                qh, kh, v, p=spec.p, chunk_size=spec_r.chunk_size,
                denom_eps=spec.denom_eps, kv_mask=kv_mask,
                init_state=None if init is None else tuple(init))
            return o.astype(q.dtype), AttnState(kv=None,
                                                moments=Moments(*state))
        _log_once(
            "prefill: fastmax kernel " + (
                "unpartitionable over 'model' (kv heads and Dv both "
                "indivisible)" if plan is None
                else "resumable (offset) chunk under a mesh")
            + " -> jnp moment scan")
    elif init is not None:
        _log_once("prefill: resumable (offset) chunk -> jnp moment scan")
    # the jnp chunked scan is sharding-aware: under feature-TP the stacked
    # chunks are pinned and the carry constrained (see _causal_scan)
    fs = feature_shard_flag(k.shape[1])
    o, final = _causal_scan(
        qh, kh, v, p=spec.p, chunk_size=spec_r.chunk_size, kv_mask=kv_mask,
        denom_eps=spec.denom_eps, feature_shard=fs, init=init)
    return o.astype(q.dtype), AttnState(kv=None, moments=final)


def keep_live(live, new, old):
    """Per-slot select over a decode-state pytree whose leaves lead with
    the slot axis: slot i takes `new` where live[i], else keeps `old`.
    `live` None keeps every slot's `new`."""
    if live is None:
        return new

    def sel(n, o):
        if n.shape[:1] != live.shape:
            raise ValueError(
                f"decode-state leaf {n.shape} does not lead with the "
                f"{live.shape[0]} slots of the live mask")
        return jnp.where(live.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)

    return jax.tree.map(sel, new, old)


def step(state: AttnState, q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         spec: AttentionSpec, *, live: Optional[jnp.ndarray] = None):
    """One-token decode. q:[B,Hq,1,D], k/v:[B,Hkv,1,*].

    softmax: append to the cache, attend over the valid prefix.
    fastmax: fold (k, v) into the moments, contract with q —
    O(D^p Dv) per head per token, independent of context length.
    `live` [B] bool: a slot that is not live keeps its state unchanged
    (the single-device decode kernel holds it in place; every other path
    selects per slot over this layer's state); None steps every slot.
    Returns (o [B,Hq,1,Dv], new AttnState).
    """
    _check_state(state, spec)
    if spec.family == "softmax":
        from repro.sharding.rules import constrain_kv_cache, model_axis_size
        kv = state.kv
        if kv.length.ndim == 0:
            # legacy shared cursor: one dynamic_update_slice for the batch
            kc = jax.lax.dynamic_update_slice_in_dim(
                kv.k, k.astype(kv.k.dtype), kv.length, axis=2)
            vc = jax.lax.dynamic_update_slice_in_dim(
                kv.v, v.astype(kv.v.dtype), kv.length, axis=2)
            mc = kv.mask
        else:
            # slot-indexed pool: per-sequence write cursors (each slot may
            # sit at a different context length) — scatter one row per
            # sequence, and mark the written row valid in the mask lane
            # (chunked prefill may have left a padding marker there)
            bidx = jnp.arange(kv.k.shape[0])
            kc = kv.k.at[bidx, :, kv.length].set(
                k[:, :, 0, :].astype(kv.k.dtype))
            vc = kv.v.at[bidx, :, kv.length].set(
                v[:, :, 0, :].astype(kv.v.dtype))
            mc = kv.mask.at[bidx, :, kv.length].set(1.0)
        # pin the freshly-updated cache to its committed inter-step layout
        # (kv_cache_spec: heads over 'model' when divisible, else the
        # sequence dim) — without this the partitioner resolves the
        # head-sharded-consumer vs head_dim-sharded-cache conflict by
        # fully rematerializing cache-sized tensors every step (the 3
        # SOFTMAX 32k-decode warnings, ROADMAP)
        kc = constrain_kv_cache(kc)
        vc = constrain_kv_cache(vc)
        nmax = kc.shape[2]
        length_b = kv.length if kv.length.ndim else kv.length[None]
        mask = (jnp.arange(nmax)[None, None, :]
                <= length_b[:, None, None]).astype(jnp.float32) * mc
        mask = constrain_kv_cache(mask)
        tp = model_axis_size()
        if tp > 1 and k.shape[1] % tp != 0:
            # sequence-sharded cache: queries must be model-replicated so
            # the softmax over the sharded timeline partitions as partial
            # max/sum reductions instead of resharding the cache
            from repro.sharding.rules import replicate
            q = replicate(q, batch_dim=0)
        o = softmax_attention(q, kc, vc, causal=False, kv_mask=mask)
        return o, keep_live(live, AttnState(
            kv=KVCache(kc, vc, kv.length + 1, mc), moments=None), state)

    qh = normalize_qk(q) if spec.normalize else q
    kh = normalize_qk(k) if spec.normalize else k
    hkv, hq = k.shape[1], q.shape[1]
    if use_decode_kernel(spec):
        from repro.kernels import ops as kernel_ops
        mesh, plan = _kernel_plan(q, k, v)
        if plan is not None:
            from repro.kernels.sharded import fastmax_decode_sharded
            o, new_state = fastmax_decode_sharded(
                qh, kh, v, tuple(state.moments), p=spec.p,
                denom_eps=spec.denom_eps, plan=plan)
            return (o.astype(q.dtype), keep_live(
                live, AttnState(kv=None, moments=Moments(*new_state)),
                state))
        if mesh is None:
            o, new_state = kernel_ops.fastmax_decode(
                qh, kh, v, state.moments, p=spec.p, denom_eps=spec.denom_eps,
                live=live)
            return (o.astype(q.dtype),
                    AttnState(kv=None, moments=Moments(*new_state)))
        _log_once(
            "decode: fastmax kernel unpartitionable over 'model' "
            "(kv heads and Dv both indivisible) -> jnp feature-TP step")
    # jnp moment step. Under tensor parallelism the moments are sharded on
    # their feature (Dv / trailing-D) dims while q arrives head-sharded —
    # constrain the delta, the running state, and the combine to consistent
    # feature-TP so XLA never rematerializes a moment-sized tensor
    # (ROADMAP serve-path item; see combine_with_queries(feature_shard=)).
    fs = feature_shard_flag(hkv)
    if fs:
        # the new token's k/v are tiny — pin them model-replicated (keeping
        # DP on batch) so every device builds ITS OWN feature slice of the
        # moment delta locally; without this the delta (full moment size!)
        # is produced head-sharded and resharded over the ICI every step
        from repro.sharding.rules import replicate
        kh = replicate(kh, batch_dim=0)
        v = replicate(v, batch_dim=0)
    delta = compute_moments(kh, v, p=spec.p)
    if fs:
        delta = _constrain_moments_j(delta)
    new_mom = state.moments + delta
    if fs:
        new_mom = _constrain_moments_j(new_mom)
    # fold the query group into the token axis (no broadcast of the state)
    qg = qh.reshape(q.shape[0], hkv, hq // hkv, q.shape[-1])
    num, den = combine_with_queries(qg, new_mom, p=spec.p, feature_shard=fs)
    new_kv = None
    w_slots = _window_slots(spec)
    if w_slots > 0:
        # hybrid near field: the moments above already weighted every
        # causal token by f_p; add the (exp - f_p) correction for the
        # in-band ones — the token itself (distance 0) and window rows
        # 1..W-1 (row r holds the token at distance W-r, so row 0 sits
        # at distance W, just out of band)
        kv = state.kv
        acc = jnp.promote_types(qg.dtype, jnp.float32)
        qf = qg.astype(acc)
        s0 = jnp.einsum("bhgd,bhtd->bhg", qf, kh.astype(acc))
        c0 = jnp.exp(s0) - poly_kernel(s0, spec.p)
        num = num + c0[..., None] * v[:, :, 0].astype(num.dtype)[:, :, None]
        den = den + c0
        sw = jnp.einsum("bhgd,bhwd->bhgw", qf, kv.k.astype(acc))
        cw = jnp.exp(sw) - poly_kernel(sw, spec.p)
        in_band = (jnp.arange(w_slots) >= 1).astype(acc)
        cw = cw * (in_band[None, None, None, :] * kv.mask[:, :, None, :])
        num = num + jnp.einsum("bhgw,bhwj->bhgj", cw,
                               kv.v.astype(acc)).astype(num.dtype)
        den = den + jnp.sum(cw, axis=-1)
        # shift-append the new token at row W-1 (most recent)
        nk = jnp.concatenate([kv.k[:, :, 1:], kh.astype(kv.k.dtype)],
                             axis=2)
        nv = jnp.concatenate([kv.v[:, :, 1:], v.astype(kv.v.dtype)],
                             axis=2)
        nm = jnp.concatenate([kv.mask[:, :, 1:],
                              jnp.ones_like(kv.mask[:, :, :1])], axis=2)
        new_kv = KVCache(nk, nv, kv.length + 1, nm)
    o = num / (den + spec.denom_eps)[..., None]
    o = o.reshape(q.shape[0], hq, 1, -1).astype(q.dtype)
    return o, keep_live(live, AttnState(kv=new_kv, moments=new_mom), state)
