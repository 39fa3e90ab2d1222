"""jit'd wrappers for the Fastmax Pallas kernels.

Dispatch policy:
  * on TPU: compiled Pallas kernels.
  * elsewhere (this CPU container, tests): interpret=True — the kernel body
    executes in Python/XLA-CPU for bit-level validation of the SAME code
    that Mosaic would compile for TPU.

Training gradients: the kernel forward is paired (via custom_vjp) with the
fused Pallas causal-backward kernel (`fastmax_causal_bwd.py`) implementing
the paper §2.5 reversible-carry recomputation in VMEM. The forward kernel
itself emits the final moment carry as the only extra residual beyond
(q, k, v) — O(D^{p+1}), not O(N D^p), and with no second jnp pass over the
sequence. The jnp chunked backward (`_causal_scan_cg_bwd`) remains wired in
as an interpret-mode oracle, selectable via REPRO_FASTMAX_BWD=jnp.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core import fastmax as _fm
from repro.core import hybrid as _hy
from repro.kernels import autotune as _at
from repro.kernels.fastmax_causal import fastmax_causal_pallas
from repro.kernels.fastmax_causal_bwd import fastmax_causal_bwd_pallas
from repro.kernels.fastmax_decode import fastmax_decode_pallas
from repro.kernels.fastmax_noncausal import fastmax_noncausal_pallas
from repro.kernels.hybrid_causal import hybrid_causal_pallas

__all__ = ["fastmax", "fastmax_prefill_kernel", "fastmax_decode",
           "fastmax_bwd", "hybrid", "use_interpret", "use_pallas_bwd"]


def use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _lookup(kernel: str, q, k, v, p: int, chunk_size: int):
    """Autotune lookup at trace time (shapes are concrete); returns None
    when REPRO_AUTOTUNE is off — the kernels then use their own pick_*
    defaults, byte-identical to an autotune-free build."""
    return _at.lookup_schedule(
        kernel, n=q.shape[2], d=q.shape[3], dv=v.shape[-1],
        g=q.shape[1] // k.shape[1], p=p, dtype=q.dtype,
        chunk_size=chunk_size)


def _causal_kwargs(sched, chunk_size: int) -> dict:
    """Schedule → fastmax_causal(_bwd)_pallas kwargs ({} keeps defaults)."""
    if sched is None:
        return {"chunk_size": chunk_size}
    return {"chunk_size": sched.chunk_size, "bm": sched.bm,
            "blk": sched.blk, "grid": sched.grid}


def _nc_kwargs(sched, chunk_size: int) -> dict:
    if sched is None:
        return {"chunk_size": chunk_size}
    return {"chunk_size": sched.chunk_size, "bm": sched.bm,
            "grid": sched.grid}


def use_pallas_bwd() -> bool:
    """Backward schedule: the fused Pallas kernel unless REPRO_FASTMAX_BWD
    selects the jnp §2.5 chunked scan (the equivalence oracle)."""
    return os.environ.get("REPRO_FASTMAX_BWD", "pallas").lower() != "jnp"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fastmax_causal_trainable(q, k, v, p, chunk_size, denom_eps, interpret,
                              sched_fwd, sched_bwd):
    # sched_fwd/sched_bwd are hashable Schedule records (or None for the
    # untuned defaults) — static nondiff args so fwd and bwd each run
    # their OWN tuned schedule (the moments are plain sums, so the two
    # sides may chunk/block the sequence independently)
    return fastmax_causal_pallas(
        q, k, v, p=p, denom_eps=denom_eps, interpret=interpret,
        **_causal_kwargs(sched_fwd, chunk_size))


def _fc_fwd(q, k, v, p, chunk_size, denom_eps, interpret, sched_fwd,
            sched_bwd):
    # the forward kernel emits its own final carry (m-major moments) — the
    # only residual the reversible backward needs beyond (q, k, v):
    # O(D^{p+1}) bytes, and no extra jnp pass over the full sequence (the
    # former `compute_moments` call here spiked peak memory at long N).
    o, state = fastmax_causal_pallas(
        q, k, v, p=p, denom_eps=denom_eps, interpret=interpret,
        return_state=True, **_causal_kwargs(sched_fwd, chunk_size))
    if p < 2:
        # don't hold the [B,Hkv,D,D,Dv] zeros placeholder live as a
        # residual — at p=1 both backwards ignore/rebuild it
        state = state[:2] + (None,) + state[3:]
    return o, (q, k, v, state)


def _fc_bwd(p, chunk_size, denom_eps, interpret, sched_fwd, sched_bwd, res,
            do):
    q, k, v, state = res
    return fastmax_bwd(q, k, v, state, do, p=p, chunk_size=chunk_size,
                       denom_eps=denom_eps, interpret=interpret,
                       schedule=sched_bwd)


def fastmax_bwd(q, k, v, state, do, *, p: int = 2, chunk_size: int = 128,
                denom_eps: float = 1e-6, interpret: bool | None = None,
                schedule=None, return_dstate: bool = False):
    """Causal fastmax backward on the kernel-emitted final carry.

    Returns (dq, dk, dv). The Dv-blocked fused Pallas kernel by default;
    REPRO_FASTMAX_BWD=jnp reroutes to the jnp §2.5 chunked reverse scan
    (the equivalence oracle and escape hatch). `state` may carry None for
    m2 at p < 2 (the custom_vjp residual drops the zeros placeholder).

    `return_dstate=True` appends the cotangent of the scan's initial carry
    (moment-layout tuple) — dC_i for a context-parallel shard whose forward
    was seeded; supported by BOTH backends so CP grads stay oracle-testable.

    Also the per-shard backward of the feature-TP trainable path
    (`repro.kernels.sharded`): on a Dv shard of (v, do, m-moments) with the
    full g-moments, every emitted dq/dk term is the shard's exact partial
    (the same additive-over-Dv decomposition the in-kernel blocking uses),
    so one psum per launch reassembles the full gradients — and that holds
    for BOTH backends here, keeping the jnp oracle comparable shard-local.
    """
    if interpret is None:
        interpret = use_interpret()
    if use_pallas_bwd():
        if schedule is None:
            schedule = _lookup("causal_bwd", q, k, v, p, chunk_size)
        return fastmax_causal_bwd_pallas(
            q, k, v, state, do, p=p, denom_eps=denom_eps,
            interpret=interpret, return_dstate=return_dstate,
            **_causal_kwargs(schedule, chunk_size))
    # jnp oracle: the §2.5 chunked reverse scan on the same kernel-emitted
    # carry (kept for equivalence testing and as an escape hatch)
    if state[2] is None or p < 2:
        d, dv = q.shape[-1], v.shape[-1]
        m2 = jnp.zeros(k.shape[:2] + (d, d, dv), state[0].dtype)
        state = tuple(state[:2]) + (m2,) + tuple(state[3:])
    return _fm._causal_scan_cg_bwd(p, chunk_size, denom_eps, False,
                                   (q, k, v, _fm.Moments(*state)), do,
                                   return_dstate=return_dstate)


_fastmax_causal_trainable.defvjp(_fc_fwd, _fc_bwd)


def fastmax(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    p: int = 2,
    causal: bool = False,
    chunk_size: int = 128,
    denom_eps: float = 1e-6,
    interpret: bool | None = None,
    schedule=None,
) -> jnp.ndarray:
    """Kernel-backed fastmax on pre-normalized q̂/k̂ (GQA-aware).

    `schedule` forces one `autotune.Schedule` on every launch (tests);
    None consults the autotuner per kernel — which itself returns None
    (the untuned `pick_*` defaults) unless REPRO_AUTOTUNE enables it.
    """
    if interpret is None:
        interpret = use_interpret()
    if causal:
        sf = schedule if schedule is not None else _lookup(
            "causal_fwd", q, k, v, p, chunk_size)
        sb = schedule if schedule is not None else _lookup(
            "causal_bwd", q, k, v, p, chunk_size)
        return _fastmax_causal_trainable(
            q, k, v, p, chunk_size, denom_eps, interpret, sf, sb)
    if schedule is None:
        schedule = _lookup("noncausal", q, k, v, p, chunk_size)
    return _fastmax_noncausal_trainable(
        q, k, v, p, chunk_size, denom_eps, interpret, schedule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fastmax_noncausal_trainable(q, k, v, p, chunk_size, denom_eps,
                                 interpret, sched):
    return fastmax_noncausal_pallas(
        q, k, v, p=p, denom_eps=denom_eps, interpret=interpret,
        **_nc_kwargs(sched, chunk_size))


def _fnc_fwd(q, k, v, p, chunk_size, denom_eps, interpret, sched):
    o = fastmax_noncausal_pallas(
        q, k, v, p=p, denom_eps=denom_eps, interpret=interpret,
        **_nc_kwargs(sched, chunk_size))
    return o, (q, k, v)


def _fnc_bwd(p, chunk_size, denom_eps, interpret, sched, res, do):
    # the two-phase noncausal kernel has no fused backward: grads come from
    # autodiff of the jnp moment path — ONE global moment sum, so residuals
    # are O(N D^p) scan chunks, never O(N^2) scores. Mathematically the
    # same function as the kernel forward (encoder attention stays
    # kernel-routed under training instead of rerouting the forward too).
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _fm.fastmax_noncausal(
            q_, k_, v_, p=p, denom_eps=denom_eps,
            chunk_size=max(chunk_size, 512)),
        q, k, v)
    return vjp(do)


_fastmax_noncausal_trainable.defvjp(_fnc_fwd, _fnc_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _hybrid_causal_trainable(q, k, v, p, window, chunk_size, denom_eps,
                             interpret, sched_fwd):
    return hybrid_causal_pallas(
        q, k, v, p=p, window=window, denom_eps=denom_eps,
        interpret=interpret, **_causal_kwargs(sched_fwd, chunk_size))


def _hc_fwd(q, k, v, p, window, chunk_size, denom_eps, interpret, sched_fwd):
    # like fastmax: the forward kernel emits the final moment carry as the
    # only residual beyond (q, k, v) — the band needs no carry, its
    # residuals (the previous chunk's k/v) are rebuilt by shifting in the
    # reverse scan
    o, state = hybrid_causal_pallas(
        q, k, v, p=p, window=window, denom_eps=denom_eps,
        interpret=interpret, return_state=True,
        **_causal_kwargs(sched_fwd, chunk_size))
    if p < 2:
        state = state[:2] + (None,) + state[3:]
    return o, (q, k, v, state)


def _hc_bwd(p, window, chunk_size, denom_eps, interpret, sched_fwd, res, do):
    q, k, v, state = res
    if state[2] is None or p < 2:
        d, dv = q.shape[-1], v.shape[-1]
        m2 = jnp.zeros(k.shape[:2] + (d, d, dv), state[0].dtype)
        state = tuple(state[:2]) + (m2,) + tuple(state[3:])
    # the backward must re-chunk exactly like the forward: w_eff depends on
    # the chunk length, so a tuned forward schedule pins the reverse scan's
    # chunk size too
    cs = sched_fwd.chunk_size if sched_fwd is not None else chunk_size
    return _hy.hybrid_bwd_scan(
        q, k, v, _fm.Moments(*state), do, p=p, window=window,
        chunk_size=cs, denom_eps=denom_eps)


_hybrid_causal_trainable.defvjp(_hc_fwd, _hc_bwd)


def hybrid(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    p: int = 2,
    window: int = 64,
    causal: bool = True,
    chunk_size: int = 128,
    denom_eps: float = 1e-6,
    interpret: bool | None = None,
    schedule=None,
) -> jnp.ndarray:
    """Kernel-backed hybrid near/far-field attention on pre-normalized
    q̂/k̂ (causal only). Forward is the fused Pallas launch
    (`hybrid_causal.py`); backward is the jnp §2.5 reverse scan extended
    with band residuals, seeded by the kernel-emitted carry. w_eff=0
    delegates to the fastmax pair for bitwise parity."""
    if not causal:
        raise ValueError("hybrid kernels are causal-only")
    if interpret is None:
        interpret = use_interpret()
    if _hy.effective_window(window, chunk_size) == 0:
        return fastmax(q, k, v, p=p, causal=True, chunk_size=chunk_size,
                       denom_eps=denom_eps, interpret=interpret,
                       schedule=schedule)
    sf = schedule if schedule is not None else _lookup(
        "hybrid_fwd", q, k, v, p, chunk_size)
    return _hybrid_causal_trainable(
        q, k, v, p, window, chunk_size, denom_eps, interpret, sf)


def fastmax_prefill_kernel(
    q, k, v, *, p: int = 2, chunk_size: int = 128, denom_eps: float = 1e-6,
    kv_mask=None, interpret: bool | None = None, schedule=None,
    init_state=None,
):
    """Kernel-backed causal prefill on pre-normalized q̂/k̂ (distinct from
    the jnp `repro.core.decode_state.fastmax_prefill`, which normalizes
    internally and returns a `Moments` NamedTuple).

    Returns (o, state): the final moment carry is emitted by the forward
    kernel itself (no recompute pass), in the layout `fastmax_decode`
    consumes natively — the prefill→decode handoff is one kernel launch.
    `init_state` seeds the scan with an existing carry (moment tuple) —
    tokens already folded by earlier context-parallel shards or an earlier
    resumable-prefill call; the outputs are then the exact causal
    continuation and the returned state includes the seed.
    """
    if interpret is None:
        interpret = use_interpret()
    if schedule is None:
        schedule = _lookup("causal_fwd", q, k, v, p, chunk_size)
    return fastmax_causal_pallas(
        q, k, v, kv_mask, p=p, denom_eps=denom_eps, interpret=interpret,
        return_state=True, init_state=init_state,
        **_causal_kwargs(schedule, chunk_size))


def fastmax_decode(
    q, k, v, state, *, p: int = 2, denom_eps: float = 1e-6,
    interpret: bool | None = None, schedule=None, live=None,
):
    """Kernel-backed single-token decode step on moment-tuple state.
    `live` [B] bool: a slot that is not live keeps its state bit for bit
    (and gets zero output rows); None means every slot is live."""
    if interpret is None:
        interpret = use_interpret()
    if schedule is None:
        schedule = _lookup("decode", q, k, v, p, 128)
    dk = {} if schedule is None else {"bm": schedule.bm,
                                      "grid": schedule.grid}
    return fastmax_decode_pallas(
        q, k, v, tuple(state), live, p=p, denom_eps=denom_eps,
        interpret=interpret, **dk)
