"""Streaming/decode state primitives for Fastmax attention.

The asymptotic punchline of FAST at inference: the recurrent state of a
fastmax attention layer is its moment tuple — size
``Hkv * (1 + D + D^2) * (Dv + 1)`` floats, INDEPENDENT of context length.
A 32k- or 500k-token context costs the same per decoded token.

NOTE: the unified decode-state protocol (`init_state`/`prefill`/`step`
over the `AttnState` union, covering the softmax KV cache too) lives in
`repro.attention.state` and subsumes this module; these functions remain
as fastmax-level primitives / back-compat shims.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.fastmax import (
    Moments,
    combine_with_queries,
    compute_moments,
    normalize_qk,
)

__all__ = ["init_fastmax_state", "fastmax_decode_step", "fastmax_prefill",
           "decode_state_bytes"]


def decode_state_bytes(cfg, batch: int, max_len: int) -> int:
    """Bytes of the full-model decode state for `batch` sequences of up to
    `max_len` tokens, WITHOUT allocating it (jax.eval_shape).

    This is the number the serving engine's slot accounting reports:
    for fastmax specs it is
    INDEPENDENT of `max_len` (constant moment tuples), for the softmax
    baseline it grows linearly (KV cache rows) — the asymmetry that lets
    `repro.serve` batch 500k-context and 64-token requests into
    identically-sized slots with no paged-KV machinery.
    """
    from repro.models import decode_state_specs  # lazy: core must not
    #                                              import models at top level
    specs = decode_state_specs(cfg, batch, max_len)
    return int(sum(s.size * jnp.dtype(s.dtype).itemsize
                   for s in jax.tree.leaves(specs)))


def init_fastmax_state(
    batch: int, h_kv: int, d: int, dv: int, *, p: int = 2,
    dtype=jnp.float32,
) -> Moments:
    """Zero moments for a fresh sequence."""
    z = lambda *s: jnp.zeros((batch, h_kv) + s, dtype)
    return Moments(z(dv), z(d, dv), z(d, d, dv), z(), z(d), z(d, d))


def fastmax_prefill(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    p: int = 2, normalize: bool = True,
    kv_mask: Optional[jnp.ndarray] = None,
    chunk_size: int = 128, denom_eps: float = 1e-6,
):
    """Causal prefill returning (outputs, final Moments) for streaming decode."""
    from repro.core.fastmax import _causal_scan  # noqa: internal reuse

    qh = normalize_qk(q) if normalize else q
    kh = normalize_qk(k) if normalize else k
    o, final = _causal_scan(qh, kh, v, p=p, chunk_size=chunk_size,
                            kv_mask=kv_mask, denom_eps=denom_eps)
    return o, final


def fastmax_decode_step(
    state: Moments,
    q: jnp.ndarray,  # [B, Hq, 1, D]
    k: jnp.ndarray,  # [B, Hkv, 1, D]
    v: jnp.ndarray,  # [B, Hkv, 1, Dv]
    *,
    p: int = 2,
    normalize: bool = True,
    denom_eps: float = 1e-6,
):
    """One decode step: fold the new (k, v) into the moments, contract with q.

    O(D^{p} Dv) per head per token — no dependence on context length.
    Returns (o [B,Hq,1,Dv], new_state).
    """
    qh = normalize_qk(q) if normalize else q
    kh = normalize_qk(k) if normalize else k
    new_state = state + compute_moments(kh, v, p=p)
    hkv = k.shape[1]
    hq = q.shape[1]
    # fold the query group into the token axis (no broadcast of the state)
    qg = qh.reshape(q.shape[0], hkv, hq // hkv, q.shape[-1])
    num, den = combine_with_queries(qg, new_state, p=p)
    o = num / (den + denom_eps)[..., None]
    return o.reshape(q.shape[0], hq, 1, -1).astype(q.dtype), new_state
