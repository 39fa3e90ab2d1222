"""shard_map wrappers: the fastmax/hybrid Pallas kernels on a mesh.

A `pallas_call` is opaque to the SPMD partitioner: under a mesh, GSPMD
treats it as a replicated computation and all-gathers every operand. These
wrappers make the kernels shard-native instead — each device runs the SAME
kernel body on its shard, with the partitioning chosen once per call site:

  heads mode    Hkv % tp == 0: batch over the DP axes ("pod","data"), kv
                heads (and their aligned query groups) over "model". Every
                kernel — forward, fused backward, decode — is embarrassingly
                parallel per (batch, kv-head), so the wrapped call has ZERO
                collectives; the only cross-device traffic left is the
                row-parallel wo psum the caller already does.
  seq mode      context parallelism for causal TRAINING: the sequence dim
                sharded over a "seq" mesh axis, each device running the
                full Pallas chunk scan on its contiguous token shard. The
                chunk fold is associative (the §2.5 reversible carry is
                built on it), so correctness needs exactly ONE constant-
                size collective per direction: forward, each device folds
                its local moments and receives the exclusive prefix sum of
                the earlier shards' moments (ppermute ring or allgather,
                picked by modeled bytes — `pick_cp_exchange`), seeding its
                kernel launch; backward, the fused kernel emits the
                cotangent of its seed (dC_i) and the suffix sum over later
                shards gives the gradient each shard's own moment delta
                receives — chained through `jax.vjp(compute_moments)`.
                Boundary traffic is O(D²·Dv) per device pair, independent
                of N — vs ring-attention's O(N·D) KV rotation
                (`cp_boundary_model` records both for the dryrun gate).
  feature mode  Hkv % tp != 0 (GQA/MQA at TP degree > Hkv) but Dv % tp == 0:
                moments and v sharded on the value-feature dim over "model"
                (the feature-TP layout of `_constrain_moments_j`), q/k and
                the scalar g-moments replicated across "model". Each device
                folds the token into ITS Dv-slice of (m0, m1, m2) and
                redundantly maintains the tiny g-moments, so the numerator
                splits tp-ways and the denominator is exact locally — zero
                collectives inside the inference wrappers (prefill forward
                + decode). TRAINING runs feature-TP too: the Dv-blocked
                fused backward decomposes additively over value-feature
                columns (every dq/dk term is linear in the block-local
                output cotangent and its denominator partial), so each
                device launches the blocked backward on its Dv shard and
                the wrapper psums the partial dq/dk ONCE per launch — the
                only collectives in the trainable path, off the per-chunk
                critical path (mathematically equal to psumming the score
                cotangent ds inside the chunk loop, without serializing a
                collective per chunk). The jnp chunked scan remains the
                REPRO_FASTMAX_BWD=jnp oracle (`attention/backends.py`).

The group alignment heads mode relies on: q heads are grouped contiguously
([B, Hkv, G, ...] reshape), so a "model" shard of Hq = G·Hkv heads is
exactly the query groups of its Hkv-shard — no regrouping traffic.

`plan_kernel_sharding` returns None when neither mode divides (the caller
falls back to the jnp feature-TP moment step, logged), and
`nontrivial_mesh()` distinguishes "no mesh at all" (plain single-device
kernel call) from "mesh but unpartitionable".
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["ShardPlan", "nontrivial_mesh", "plan_kernel_sharding",
           "fastmax_sharded", "fastmax_prefill_sharded",
           "fastmax_decode_sharded", "hybrid_sharded", "pick_cp_exchange",
           "cp_carry_bytes", "cp_boundary_model"]


class ShardPlan(NamedTuple):
    """How one fastmax kernel call partitions over the active mesh."""

    mesh: object            # jax.sharding.Mesh
    batch: object           # P entry for the batch dim: None | axis | tuple
    mode: str               # "heads" | "feature" | "seq"
    tp: int                 # size of the "model" axis (1 = no TP)
    cp: int = 1             # size of the "seq" axis (1 = no CP)

    @property
    def head(self):
        return "model" if (self.mode == "heads" and self.tp > 1) else None

    @property
    def feat(self):
        return "model" if self.mode == "feature" else None

    def describe(self) -> str:
        mesh_s = "x".join(f"{a}={self.mesh.shape[a]}"
                          for a in self.mesh.axis_names)
        return f"shard_map[{self.mode}] over ({mesh_s})"


def nontrivial_mesh():
    """The active mesh when any axis has size > 1, else None."""
    from repro.sharding.rules import active_mesh

    mesh = active_mesh()
    if mesh is None:
        return None
    if all(mesh.shape[a] == 1 for a in mesh.axis_names):
        return None
    return mesh


def plan_kernel_sharding(mesh, *, batch: int, hq: int, hkv: int,
                         dv: int, seq_len: int | None = None,
                         ) -> Optional[ShardPlan]:
    """Pick the partitioning for a fastmax kernel call, or None.

    None means the mesh tensor-parallelizes over "model" but neither kv
    heads nor the value-feature dim divide it — the caller should use the
    jnp moment path, whose with_sharding_constraint layout degrades
    gracefully per dim. Any other mesh gets a plan, possibly degenerate
    (no 'model' axis, batch indivisible -> an all-replicated wrap), so the
    kernels stay the path whenever they CAN run.

    `seq_len` opts into seq mode (context parallelism): callers pass it
    only for causal TRAINING-shaped calls on a mesh with a "seq" axis of
    size > 1 dividing it. CP×TP composition is deferred: with tp > 1 the
    head/feature modes win and the seq axis is simply unused (replicated —
    still correct, just not context-parallel). Decode/prefill callers
    never pass seq_len, so under a pure-CP mesh they get the degenerate
    heads plan and the kernels stay the path.
    """
    if mesh is None:
        return None
    from repro.sharding.rules import _batch_entry

    tp = mesh.shape["model"] if "model" in mesh.axis_names else 1
    cp = mesh.shape["seq"] if "seq" in mesh.axis_names else 1
    b_entry, _ = _batch_entry(mesh, batch)
    if tp > 1:
        if hkv % tp == 0 and hq % tp == 0:
            mode = "heads"
        elif dv % tp == 0:
            mode = "feature"
        else:
            return None
    elif cp > 1 and seq_len is not None and seq_len % cp == 0:
        mode = "seq"
        return ShardPlan(mesh=mesh, batch=b_entry, mode=mode, tp=tp, cp=cp)
    else:
        mode = "heads"   # degenerate: DP-only wrap, heads unsharded
    return ShardPlan(mesh=mesh, batch=b_entry, mode=mode, tp=tp)


def _moment_specs(plan: ShardPlan):
    """In/out PartitionSpecs of a Moments-layout tuple [B,Hkv,...]."""
    ba, h, f = plan.batch, plan.head, plan.feat
    return (
        P(ba, h, f),                    # m0 [B,Hkv,Dv]
        P(ba, h, None, f),              # m1 [B,Hkv,D,Dv]
        P(ba, h, None, None, f),        # m2 [B,Hkv,D,D,Dv]
        P(ba, h),                       # g0 [B,Hkv]
        P(ba, h, None),                 # g1 [B,Hkv,D]
        P(ba, h, None, None),           # g2 [B,Hkv,D,D]
    )


# ---------------------------------------------------------------------------
# Context parallelism (seq mode)
# ---------------------------------------------------------------------------

# temp-memory budget for the allgather exchange: gathering cp carries
# materializes cp × carry_bytes per device; past this, take the ring's
# cp-1 sequential constant-size hops instead
_CP_ALLGATHER_BUDGET = 256 * 1024 * 1024


def cp_carry_bytes(*, b: int, hkv: int, d: int, dv: int, p: int,
                   itemsize: int = 4) -> int:
    """Bytes of ONE device's exchanged moment carry (the per-boundary
    payload). m2/g2 exist only at p >= 2 — at p = 1 they are zeros the
    exchange skips."""
    elems = dv + d * dv + 1 + d
    if p >= 2:
        elems += d * d * dv + d * d
    return b * hkv * elems * itemsize


def pick_cp_exchange(cp: int, carry_bytes: int) -> str:
    """'allgather' (one collective, cp·carry_bytes temp) under the budget,
    else 'ring' (cp-1 ppermute hops, constant memory). REPRO_CP_EXCHANGE
    overrides: auto|ring|allgather (the two differ in summation ORDER, so
    tests compare them under allclose, not bitwise)."""
    forced = os.environ.get("REPRO_CP_EXCHANGE", "auto").lower()
    if forced in ("ring", "allgather"):
        return forced
    return "allgather" if cp * carry_bytes <= _CP_ALLGATHER_BUDGET else "ring"


def cp_boundary_model(*, n: int, b: int, hkv: int, d: int, dv: int, p: int,
                      cp: int, itemsize: int = 4) -> dict:
    """Modeled per-boundary collective bytes: the CP carry exchange vs the
    ring-attention alternative (each boundary step rotates a neighbor's
    K/V shard of n/cp tokens — O(N·D), growing with sequence length; the
    moment carry is O(D²·Dv), independent of N). Recorded in the dryrun
    cell JSON so the gate can assert N-independence."""
    carry = cp_carry_bytes(b=b, hkv=hkv, d=d, dv=dv, p=p, itemsize=itemsize)
    ring_attn = b * hkv * (n // max(cp, 1)) * (d + dv) * itemsize
    return {
        "cp": cp,
        "exchange": pick_cp_exchange(cp, carry),
        "carry_bytes_per_boundary": carry,
        "ring_attention_bytes_per_boundary": ring_attn,
        "carry_to_ring_ratio": carry / ring_attn if ring_attn else None,
    }


def _cp_prefix_sum(leaves: tuple, cp: int, impl: str, reverse: bool = False):
    """EXCLUSIVE prefix (Σ_{j<i}; reverse=True: suffix Σ_{j>i}) sum of
    per-device arrays over the "seq" axis. Runs inside a shard_map body.

    allgather: one collective + a masked contraction. ring: cp-1
    sequential ppermute hops — after s hops device i holds shard i∓s's
    leaves and folds them iff that shard is on the correct side (no
    wraparound contribution is ever included)."""
    import jax.numpy as jnp

    idx = jax.lax.axis_index("seq")
    if impl == "allgather":
        ar = jnp.arange(cp)
        sel = (ar > idx) if reverse else (ar < idx)

        def one(x):
            g = jax.lax.all_gather(x, "seq")             # [cp, ...]
            return jnp.tensordot(sel.astype(g.dtype), g, axes=1)

        return tuple(one(x) for x in leaves)
    shift = -1 if reverse else 1
    perm = [(j, (j + shift) % cp) for j in range(cp)]
    acc = tuple(jnp.zeros_like(x) for x in leaves)
    msg = leaves
    for s in range(1, cp):
        msg = tuple(jax.lax.ppermute(x, "seq", perm) for x in msg)
        take = (idx < cp - s) if reverse else (idx >= s)
        acc = tuple(a + jnp.where(take, m, jnp.zeros_like(m))
                    for a, m in zip(acc, msg))
    return acc


def _seq_state_specs(ba):
    """Specs of the stacked per-shard carry [cp(seq), B, Hkv, ...] — each
    shard's final moments differ, so the residual keeps them under a
    leading "seq"-sharded axis instead of pretending replication."""
    return (
        P("seq", ba, None, None),                # m0 [cp,B,Hkv,Dv]
        P("seq", ba, None, None, None),          # m1
        P("seq", ba, None, None, None, None),    # m2
        P("seq", ba, None),                      # g0
        P("seq", ba, None, None),                # g1
        P("seq", ba, None, None, None),          # g2
    )


def _seq_fwd_launch(q, k, v, p, chunk_size, denom_eps, plan, schedule):
    """Seq-mode forward: (o, stacked per-shard final carries).

    Per device: fold the local shard's moments (jnp chunked fold — same
    flop order as the kernel's, memory-bounded), ONE exclusive-prefix
    exchange of the constant-size carry, then a single seeded Pallas
    launch whose outputs are the exact causal outputs of the full
    sequence restricted to this shard.
    """
    import jax.numpy as jnp

    from repro.core.fastmax import compute_moments_chunked
    from repro.kernels import ops as kernel_ops

    b, hq, n, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    ba, cp = plan.batch, plan.cp
    impl = pick_cp_exchange(
        cp, cp_carry_bytes(b=b, hkv=hkv, d=d, dv=dv, p=p))
    shard4 = P(ba, None, "seq", None)

    def body(q, k, v):
        mom = compute_moments_chunked(k, v, p=p, chunk_size=chunk_size)
        live = tuple(mom) if p >= 2 else (mom[0], mom[1], mom[3], mom[4])
        carry = _cp_prefix_sum(live, cp, impl)
        if p < 2:
            carry = (carry[0], carry[1], jnp.zeros_like(mom[2]),
                     carry[2], carry[3], jnp.zeros_like(mom[5]))
        o, state = kernel_ops.fastmax_prefill_kernel(
            q, k, v, p=p, chunk_size=chunk_size, denom_eps=denom_eps,
            schedule=schedule, init_state=carry)
        return o, tuple(x[None] for x in state)

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(shard4, shard4, shard4),
        out_specs=(shard4, _seq_state_specs(ba)),
        check_vma=False,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _seq_trainable(q, k, v, p, chunk_size, denom_eps, plan, schedule):
    o, _ = _seq_fwd_launch(q, k, v, p, chunk_size, denom_eps, plan,
                           schedule)
    return o


def _st_fwd(q, k, v, p, chunk_size, denom_eps, plan, schedule):
    o, state = _seq_fwd_launch(q, k, v, p, chunk_size, denom_eps, plan,
                               schedule)
    if p < 2:
        # don't hold the [cp,B,Hkv,D,D,Dv] zeros placeholder as a residual
        state = state[:2] + (None,) + state[3:]
    return o, (q, k, v, tuple(state))


def _st_bwd(p, chunk_size, denom_eps, plan, schedule, res, do):
    q, k, v, state = res
    from repro.core.fastmax import compute_moments_chunked
    from repro.kernels import ops as kernel_ops

    b, hq, n, d = q.shape
    hkv, dv = k.shape[1], v.shape[-1]
    ba, cp = plan.batch, plan.cp
    impl = pick_cp_exchange(
        cp, cp_carry_bytes(b=b, hkv=hkv, d=d, dv=dv, p=p))
    shard4 = P(ba, None, "seq", None)
    sspecs = _seq_state_specs(ba)
    no_m2 = state[2] is None
    if no_m2:
        state, sspecs = state[:2] + state[3:], sspecs[:2] + sspecs[3:]

    def body(q, k, v, do, *state):
        import jax.numpy as jnp

        state = tuple(x[0] for x in state)      # strip the stacked seq lead
        if no_m2:
            state = state[:2] + (None,) + state[2:]
        # local fused backward on the SEEDED forward's final carry: the
        # reversible subtraction reconstructs down to the seed, so dq/dk/dv
        # are this shard's exact local grads and dC the seed's cotangent
        dq, dk, dvv, dC = kernel_ops.fastmax_bwd(
            q, k, v, state, do, p=p, chunk_size=chunk_size,
            denom_eps=denom_eps, schedule=schedule, return_dstate=True)
        # one suffix exchange: later shards' seeds contain THIS shard's
        # moment delta, so Σ_{j>i} dC_j is the gradient it receives
        live = (tuple(dC) if p >= 2
                else (dC[0], dC[1], dC[3], dC[4]))
        dM = _cp_prefix_sum(live, cp, impl, reverse=True)

        def moments_fn(kk, vv):
            mom = compute_moments_chunked(kk, vv, p=p,
                                          chunk_size=chunk_size)
            return (tuple(mom) if p >= 2
                    else (mom[0], mom[1], mom[3], mom[4]))

        prim, vjp_fn = jax.vjp(moments_fn, k, v)
        dM = tuple(x.astype(y.dtype) for x, y in zip(dM, prim))
        dk_x, dv_x = vjp_fn(dM)
        acc = jnp.promote_types(q.dtype, jnp.float32)
        dk = (dk.astype(acc) + dk_x.astype(acc)).astype(k.dtype)
        dvv = (dvv.astype(acc) + dv_x.astype(acc)).astype(v.dtype)
        return dq, dk, dvv

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(shard4, shard4, shard4, shard4, *sspecs),
        out_specs=(shard4, shard4, shard4),
        check_vma=False,
    )(q, k, v, do, *state)


_seq_trainable.defvjp(_st_fwd, _st_bwd)


def fastmax_sharded(q, k, v, *, p: int, causal: bool, chunk_size: int,
                    denom_eps: float, plan: ShardPlan, schedule=None):
    """shard_map-wrapped TRAINABLE kernel attention.

    heads mode: autodiff of the shard_map applies the per-shard custom_vjp,
    so the fused Pallas backward runs shard-local per (batch, kv-head) with
    zero collectives. feature mode (causal only): the Dv-blocked kernels
    run per value-feature shard through an explicit custom_vjp — forward
    emits the Dv-sharded outputs + moment carry collective-free, backward
    launches the blocked kernel on each shard's (v, do, m-moments) slice
    and psums the partial dq/dk once per launch (see module docstring).

    `schedule` (an `autotune.Schedule` or None) forces one schedule on
    every per-shard launch; None lets the in-body autotune lookup key on
    the SHARD-LOCAL shapes — the ones the per-device kernels actually run.
    """
    if plan.mode == "heads":
        from repro.kernels import ops as kernel_ops

        ba, h = plan.batch, plan.head
        qkv_spec = P(ba, h, None, None)

        def body(q, k, v):
            return kernel_ops.fastmax(q, k, v, p=p, causal=causal,
                                      chunk_size=chunk_size,
                                      denom_eps=denom_eps,
                                      schedule=schedule)

        return shard_map(
            body, mesh=plan.mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=P(ba, h, None, None),
            check_vma=False,
        )(q, k, v)
    if plan.mode == "seq":
        if not causal:
            raise ValueError(
                "seq-mode (context-parallel) shard_map is causal-only")
        return _seq_trainable(q, k, v, p, chunk_size, denom_eps, plan,
                              schedule)
    if not causal:
        # feature mode, noncausal: shard_map wrap of the two-phase
        # noncausal kernel. The global moments are Dv-decomposable and its
        # denominator comes from the replicated k, so each device's launch
        # on its (q, k, v-slice) yields the exact Dv slice of the output
        # with zero collectives. Training works through plain autodiff of
        # this wrap: the op pairs the kernel forward with the jnp moment
        # backward (`ops._fastmax_noncausal_trainable`), each shard's
        # dq/dk are exact partials over its Dv columns, and shard_map's
        # transpose psums the replicated inputs' cotangents.
        from repro.kernels import ops as kernel_ops

        ba, f = plan.batch, plan.feat
        rep4 = P(ba, None, None, None)

        def nc_body(q, k, v):
            return kernel_ops.fastmax(q, k, v, p=p, causal=False,
                                      chunk_size=chunk_size,
                                      denom_eps=denom_eps,
                                      schedule=schedule)

        return shard_map(
            nc_body, mesh=plan.mesh,
            in_specs=(rep4, rep4, P(ba, None, None, f)),
            out_specs=P(ba, None, None, f),
            check_vma=False,
        )(q, k, v)
    return _feature_trainable(q, k, v, p, chunk_size, denom_eps, plan,
                              schedule)


def _feature_fwd_launch(q, k, v, p, chunk_size, denom_eps, plan, schedule):
    """Forward launch of the feature-mode trainable: (o, final carry).

    One shard_map of the state-emitting causal kernel: v and the emitted
    m-moments/outputs Dv-sharded, q/k and the g-moments replicated — the
    same zero-collective partitioning as `fastmax_prefill_sharded`, reused
    here so the custom_vjp residual is the kernel-emitted carry (no second
    pass) already in the layout the per-shard backward consumes.
    """
    from repro.kernels import ops as kernel_ops

    ba, f = plan.batch, plan.feat
    rep4 = P(ba, None, None, None)

    def body(q, k, v):
        return kernel_ops.fastmax_prefill_kernel(
            q, k, v, p=p, chunk_size=chunk_size, denom_eps=denom_eps,
            schedule=schedule)

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(rep4, rep4, P(ba, None, None, f)),
        out_specs=(P(ba, None, None, f), _moment_specs(plan)),
        check_vma=False,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _feature_trainable(q, k, v, p, chunk_size, denom_eps, plan, schedule):
    # primal (non-differentiated calls): the STATELESS kernel — no carry
    # DMA'd to HBM and the forward's nb grid axis stays parallel; only the
    # vjp forward below pays for state emission (it IS the residual)
    from repro.kernels import ops as kernel_ops

    ba, f = plan.batch, plan.feat
    rep4 = P(ba, None, None, None)

    def body(q, k, v):
        return kernel_ops.fastmax(q, k, v, p=p, causal=True,
                                  chunk_size=chunk_size,
                                  denom_eps=denom_eps, schedule=schedule)

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(rep4, rep4, P(ba, None, None, f)),
        out_specs=P(ba, None, None, f),
        check_vma=False,
    )(q, k, v)


def _ft_fwd(q, k, v, p, chunk_size, denom_eps, plan, schedule):
    o, state = _feature_fwd_launch(q, k, v, p, chunk_size, denom_eps, plan,
                                   schedule)
    if p < 2:
        # don't hold the [B,Hkv,D,D,Dv] zeros placeholder live as a residual
        state = state[:2] + (None,) + state[3:]
    return o, (q, k, v, tuple(state))


def _ft_bwd(p, chunk_size, denom_eps, plan, schedule, res, do):
    q, k, v, state = res
    from repro.kernels import ops as kernel_ops

    ba, f = plan.batch, plan.feat
    rep4 = P(ba, None, None, None)
    mspecs = _moment_specs(plan)
    # p < 2: the residual dropped the m2 zeros placeholder — don't rebuild
    # it at global size just to shard it in; pass the 5 live leaves and let
    # fastmax_bwd handle the None (the Pallas kernel never reads m2 at
    # p < 2, the jnp-oracle branch rebuilds shard-local zeros itself)
    no_m2 = state[2] is None
    if no_m2:
        state, mspecs = state[:2] + state[3:], mspecs[:2] + mspecs[3:]

    def body(q, k, v, do, *state):
        if no_m2:
            state = state[:2] + (None,) + state[2:]
        # the local launch sees the shard's Dv slice of (v, do, m-moments)
        # and the full g-moments: its dq/dk are the shard's exact partials
        # (fastmax_bwd docstring), its dv the shard's exact slice
        dq, dk, dv = kernel_ops.fastmax_bwd(
            q, k, v, tuple(state), do, p=p, chunk_size=chunk_size,
            denom_eps=denom_eps, schedule=schedule)
        dq = jax.lax.psum(dq, "model")
        dk = jax.lax.psum(dk, "model")
        return dq, dk, dv

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(rep4, rep4, P(ba, None, None, f), P(ba, None, None, f),
                  *mspecs),
        out_specs=(rep4, rep4, P(ba, None, None, f)),
        check_vma=False,
    )(q, k, v, do, *state)


_feature_trainable.defvjp(_ft_fwd, _ft_bwd)


# ---------------------------------------------------------------------------
# Hybrid near/far-field (banded softmax + moments) — heads/feature modes
# ---------------------------------------------------------------------------


def _hybrid_sched(q, k, v, p, chunk_size, schedule):
    """Shard-local schedule for a hybrid launch + the chunk size its jnp
    backward must re-chunk with (w_eff depends on the chunk length, so
    forward and backward are pinned to ONE chunk size — deterministic
    lookup keeps the vjp-fwd and vjp-bwd bodies consistent)."""
    from repro.kernels import ops as kernel_ops

    sched = schedule if schedule is not None else kernel_ops._lookup(
        "hybrid_fwd", q, k, v, p, chunk_size)
    return sched, (sched.chunk_size if sched is not None else chunk_size)


def _hybrid_feature_fwd_launch(q, k, v, p, window, chunk_size, denom_eps,
                               plan, schedule):
    """Feature-mode hybrid forward: (o, final moment carry), both
    Dv-sharded; q/k and the g-moments replicated — the same
    zero-collective partitioning as `_feature_fwd_launch` (the band's
    denominator terms come entirely from the replicated q/k, so each
    device's output slice is exact)."""
    from repro.kernels import ops as kernel_ops
    from repro.kernels.hybrid_causal import hybrid_causal_pallas

    ba, f = plan.batch, plan.feat
    rep4 = P(ba, None, None, None)
    interpret = kernel_ops.use_interpret()

    def body(q, k, v):
        sched, _ = _hybrid_sched(q, k, v, p, chunk_size, schedule)
        return hybrid_causal_pallas(
            q, k, v, p=p, window=window, denom_eps=denom_eps,
            interpret=interpret, return_state=True,
            **kernel_ops._causal_kwargs(sched, chunk_size))

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(rep4, rep4, P(ba, None, None, f)),
        out_specs=(P(ba, None, None, f), _moment_specs(plan)),
        check_vma=False,
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _hybrid_feature_trainable(q, k, v, p, window, chunk_size, denom_eps,
                              plan, schedule):
    # primal: the stateless fused launch (no carry DMA'd to HBM); only the
    # vjp forward pays for state emission — it IS the residual
    from repro.kernels import ops as kernel_ops

    ba, f = plan.batch, plan.feat
    rep4 = P(ba, None, None, None)

    def body(q, k, v):
        return kernel_ops.hybrid(q, k, v, p=p, window=window, causal=True,
                                 chunk_size=chunk_size, denom_eps=denom_eps,
                                 schedule=schedule)

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(rep4, rep4, P(ba, None, None, f)),
        out_specs=P(ba, None, None, f),
        check_vma=False,
    )(q, k, v)


def _hft_fwd(q, k, v, p, window, chunk_size, denom_eps, plan, schedule):
    o, state = _hybrid_feature_fwd_launch(q, k, v, p, window, chunk_size,
                                          denom_eps, plan, schedule)
    if p < 2:
        state = state[:2] + (None,) + state[3:]
    return o, (q, k, v, tuple(state))


def _hft_bwd(p, window, chunk_size, denom_eps, plan, schedule, res, do):
    q, k, v, state = res
    from repro.core import fastmax as _fm
    from repro.core.hybrid import hybrid_bwd_scan

    ba, f = plan.batch, plan.feat
    rep4 = P(ba, None, None, None)
    mspecs = _moment_specs(plan)
    no_m2 = state[2] is None
    if no_m2:
        state, mspecs = state[:2] + state[3:], mspecs[:2] + mspecs[3:]

    def body(q, k, v, do, *state):
        import jax.numpy as jnp

        if no_m2:
            d, dvl = q.shape[-1], v.shape[-1]
            m2 = jnp.zeros(k.shape[:2] + (d, d, dvl), state[0].dtype)
            state = state[:2] + (m2,) + state[2:]
        # the band-extended §2.5 reverse scan on the shard's Dv slice of
        # (v, do, m-moments): every dq/dk term (band corrections included)
        # is linear in the block-local output cotangent with an exact
        # local denominator, so one psum per launch reassembles them
        _, cs = _hybrid_sched(q, k, v, p, chunk_size, schedule)
        dq, dk, dv = hybrid_bwd_scan(
            q, k, v, _fm.Moments(*state), do, p=p, window=window,
            chunk_size=cs, denom_eps=denom_eps)
        dq = jax.lax.psum(dq, "model")
        dk = jax.lax.psum(dk, "model")
        return dq, dk, dv

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(rep4, rep4, P(ba, None, None, f), P(ba, None, None, f),
                  *mspecs),
        out_specs=(rep4, rep4, P(ba, None, None, f)),
        check_vma=False,
    )(q, k, v, do, *state)


_hybrid_feature_trainable.defvjp(_hft_fwd, _hft_bwd)


def hybrid_sharded(q, k, v, *, p: int, window: int, chunk_size: int,
                   denom_eps: float, plan: ShardPlan, schedule=None):
    """shard_map-wrapped TRAINABLE hybrid kernel attention (causal only).

    heads mode: the fused hybrid launch runs shard-local per (batch,
    kv-head) — autodiff of the shard_map applies the per-shard custom_vjp
    (fused forward + jnp band-extended reverse scan), zero collectives.
    feature mode: an explicit custom_vjp mirroring `_feature_trainable` —
    forward emits the Dv-sharded outputs + moment carry collective-free,
    backward runs the band-extended jnp reverse scan on each shard's
    slice and psums the partial dq/dk once per launch.
    """
    if plan.mode == "heads":
        from repro.kernels import ops as kernel_ops

        ba, h = plan.batch, plan.head
        qkv_spec = P(ba, h, None, None)

        def body(q, k, v):
            return kernel_ops.hybrid(q, k, v, p=p, window=window,
                                     causal=True, chunk_size=chunk_size,
                                     denom_eps=denom_eps, schedule=schedule)

        return shard_map(
            body, mesh=plan.mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=P(ba, h, None, None),
            check_vma=False,
        )(q, k, v)
    if plan.mode != "feature":
        raise ValueError(
            f"hybrid_sharded supports heads/feature modes, got "
            f"{plan.mode!r}")
    return _hybrid_feature_trainable(q, k, v, p, window, chunk_size,
                                     denom_eps, plan, schedule)


def fastmax_prefill_sharded(q, k, v, *, p: int, chunk_size: int,
                            denom_eps: float, kv_mask=None,
                            plan: ShardPlan, schedule=None):
    """shard_map-wrapped causal prefill kernel: (o, final moment tuple).

    heads mode: everything head-local. feature mode: v and the m-moments
    live on Dv-slices; q/k/g-moments are replicated over "model" (each
    device maintains the identical tiny g state), so the launch is
    collective-free and the outputs come back Dv-sharded — exactly the
    layout `decode_state_shardings` commits between steps.
    """
    import jax.numpy as jnp

    from repro.kernels import ops as kernel_ops

    ba, h, f = plan.batch, plan.head, plan.feat
    in_specs = [P(ba, h, None, None),    # q
                P(ba, h, None, None),    # k
                P(ba, h, None, f)]       # v
    args = [q, k, v]
    if kv_mask is not None:
        if h is not None and kv_mask.shape[1] == 1:
            kv_mask = jnp.broadcast_to(
                kv_mask, (kv_mask.shape[0], k.shape[1], kv_mask.shape[2]))
        in_specs.append(P(ba, h, None))
        args.append(kv_mask)

    def body(q, k, v, *rest):
        mask = rest[0] if rest else None
        return kernel_ops.fastmax_prefill_kernel(
            q, k, v, p=p, chunk_size=chunk_size, denom_eps=denom_eps,
            kv_mask=mask, schedule=schedule)

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(ba, h, None, f), _moment_specs(plan)),
        check_vma=False,
    )(*args)


def fastmax_decode_sharded(q, k, v, state, *, p: int, denom_eps: float,
                           plan: ShardPlan, schedule=None):
    """shard_map-wrapped fused decode step: (o, new moment tuple).

    The serving hot loop at TP > 1: per step each device streams only ITS
    moment shard (1/tp of m2 in feature mode; its heads in heads mode) —
    the HBM traffic the fused kernel exists to minimize now also splits
    tp-ways, with no collectives inside the step.
    """
    from repro.kernels import ops as kernel_ops

    ba, h, f = plan.batch, plan.head, plan.feat
    mspecs = _moment_specs(plan)

    def body(q, k, v, *state):
        return kernel_ops.fastmax_decode(q, k, v, tuple(state), p=p,
                                         denom_eps=denom_eps,
                                         schedule=schedule)

    return shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(ba, h, None, None),   # q
                  P(ba, h, None, None),   # k
                  P(ba, h, None, f),      # v
                  *mspecs),
        out_specs=(P(ba, h, None, f), mspecs),
        check_vma=False,
    )(q, k, v, *tuple(state))
