"""Pallas TPU kernel: single-token Fastmax decode step.

The serving hot loop. State = moment tuple (O(1) in context length,
DESIGN.md §2). Per step and kv-head this kernel:

  1. folds the new (k̂, v) into the moments (rank-1 update of m2, streamed
     in m-blocks so the [D·D, Dv] tensor is read+written exactly once),
  2. contracts φ(q̂) of the G grouped query heads against the updated
     moments (the [G, bm·D] @ [bm·D, Dv] matmuls ride the same m2 stream).

Decode is memory-bound on streaming m2 (D²·Dv·4 bytes ≈ 8 MB/head for
D=Dv=128); fusing update+combine halves HBM traffic vs two separate ops and
is why this kernel exists. HBM state buffers are reused in place via
input_output_aliases.

A per-slot live flag (one int32 per (slot, kv head) program, in SMEM,
after the other operands) lets the serving tick run the step over the
whole slot pool: a program whose slot is not live writes its moment
blocks back unchanged and emits zero output rows, so no second pass over
the pool has to restore the slots that did not decode.

The degree-2 rows of m-block `mb` are built from q̂ᵀ [D, G] and k̂ as a
[D, 1] column, both passed in that layout, so the block's dynamic offset
selects sublane rows of a ref (`fastmax_causal._outer_rows`) — Mosaic does
not lower a dynamic lane slice of a value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fastmax_causal import _outer_rows, _tn_dot, compiler_params
from repro.kernels.tiling import pick_bm

__all__ = ["fastmax_decode_pallas"]


def _decode_kernel(q_ref, k_ref, v_ref,
                   m0_ref, m1_ref, m2_ref, g0_ref, g1_ref, g2_ref,
                   qt_ref, kc_ref, live_ref,
                   o_ref, m0o, m1o, m2o, g0o, g1o, g2o,
                   acc_s, den_s, *, p, bm, nmb, denom_eps, acc):
    mb = pl.program_id(1)
    live = live_ref[pl.program_id(0)] != 0
    g, d = q_ref.shape[1], q_ref.shape[2]
    dv = v_ref.shape[2]

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(acc)       # [G, D]
        k = k_ref[0, 0].astype(acc)    # [D]
        v = v_ref[0, 0].astype(acc)    # [Dv]

        @pl.when(mb == 0)
        def _small():
            m0 = m0_ref[0] + v[None, :]
            m1 = m1_ref[0] + k[:, None] * v[None, :]
            g0 = g0_ref[0] + 1.0
            g1 = g1_ref[0] + k[None, :]
            m0o[0], m1o[0], g0o[0], g1o[0] = m0, m1, g0, g1
            num = jnp.broadcast_to(m0, (g, dv)) + jnp.dot(
                q, m1, preferred_element_type=acc)
            den = g0[0, 0] + jnp.dot(q, g1[0], preferred_element_type=acc)
            if p >= 2:
                g2 = g2_ref[0] + k[:, None] * k[None, :]
                g2o[0] = g2
                den = den + 0.5 * jnp.sum(
                    jnp.dot(q, g2, preferred_element_type=acc) * q, axis=-1)
            else:
                g2o[0] = g2_ref[0]
                m2o[0] = m2_ref[0]
            acc_s[...] = num
            den_s[...] = den[:, None]

        if p >= 2:
            t = _outer_rows(kc_ref, mb, bm)                     # [bm*D, 1]
            m2 = m2_ref[0] + t * v[None, :]                      # [bm*D, Dv]
            m2o[0] = m2
            y = _outer_rows(qt_ref, mb, bm)                     # [bm*D, G]
            acc_s[...] += 0.5 * _tn_dot(y, m2, acc)

        @pl.when(mb == nmb - 1)
        def _emit():
            o_ref[0] = (acc_s[...] / (den_s[...] + denom_eps)).astype(
                o_ref.dtype)

    # a slot that is not live streams its state back unchanged (the
    # outputs alias the inputs, so every block must still be written)
    @pl.when(jnp.logical_not(live))
    def _hold():
        @pl.when(mb == 0)
        def _small():
            m0o[0], m1o[0], g0o[0] = m0_ref[0], m1_ref[0], g0_ref[0]
            g1o[0], g2o[0] = g1_ref[0], g2_ref[0]

        m2o[0] = m2_ref[0]

        @pl.when(mb == nmb - 1)
        def _emit():
            o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("p", "denom_eps", "interpret", "out_dtype",
                              "bm", "grid")
)
def fastmax_decode_pallas(
    q: jnp.ndarray,   # [B, Hq, 1, D]   pre-normalized q̂ of the new token
    k: jnp.ndarray,   # [B, Hkv, 1, D]  pre-normalized k̂
    v: jnp.ndarray,   # [B, Hkv, 1, Dv]
    state: tuple,     # Moments with shapes [B,Hkv,Dv],[B,Hkv,D,Dv],
                      # [B,Hkv,D,D,Dv],[B,Hkv],[B,Hkv,D],[B,Hkv,D,D]
    live: jnp.ndarray | None = None,  # [B] bool; None = every slot live
    *,
    p: int = 2,
    denom_eps: float = 1e-6,
    interpret: bool = False,
    out_dtype=None,
    bm: int | None = None,
    grid: str | None = None,
):
    b, hq, _, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    g = hq // hkv
    out_dtype = out_dtype or q.dtype
    m0, m1, m2, g0, g1, g2 = state
    bh = b * hkv

    acc = jnp.promote_types(q.dtype, jnp.float32)
    qr = q.reshape(b, hkv, g, d).reshape(bh, g, d)
    # 2-D [BH·D, ·] so each head's block is a plain [D, ·] ref
    qt = jnp.swapaxes(qr, 1, 2).reshape(bh * d, g).astype(acc)
    kr = k.reshape(bh, 1, d)
    kc = kr.reshape(bh * d, 1).astype(acc)
    vr = v.reshape(bh, 1, dv)
    m0r = m0.reshape(bh, 1, dv).astype(acc)
    m1r = m1.reshape(bh, d, dv).astype(acc)
    if p >= 2:
        m2r = m2.reshape(bh, d * d, dv).astype(acc)
    else:
        m2r = jnp.zeros((bh, 1, dv), acc)  # dummy, passed through
    g0r = g0.reshape(bh, 1, 1).astype(acc)
    g1r = g1.reshape(bh, 1, d).astype(acc)
    g2r = g2.reshape(bh, d, d).astype(acc)
    if live is None:
        live = jnp.ones((b,), jnp.int32)
    # one flag per (slot, kv head) program, read as a scalar from SMEM
    lv = jnp.repeat(live.astype(jnp.int32), hkv)

    if bm is None:
        bm = pick_bm(d)
    if d % bm:
        raise ValueError(f"bm={bm} must divide D={d}")
    if grid is None:
        grid = "parallel"
    if grid not in ("parallel", "arbitrary"):
        raise ValueError(f"grid={grid!r}; expected 'parallel'|'arbitrary'")
    nmb = d // bm if p >= 2 else 1
    m2_rows = bm * d if p >= 2 else 1

    kernel = functools.partial(_decode_kernel, p=p, bm=bm, nmb=nmb,
                               denom_eps=denom_eps, acc=acc)
    sm = lambda h, mb: (h, 0, 0)          # noqa: E731 small/state blocks
    mm = lambda h, mb: (h, mb, 0)         # noqa: E731 m2 m-blocks
    outs = pl.pallas_call(
        kernel,
        grid=(bh, nmb),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda h, mb: (h, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h, mb: (h, 0, 0)),
            pl.BlockSpec((1, 1, dv), lambda h, mb: (h, 0, 0)),
            pl.BlockSpec((1, 1, dv), sm),
            pl.BlockSpec((1, d, dv), sm),
            pl.BlockSpec((1, m2_rows, dv), mm),
            pl.BlockSpec((1, 1, 1), sm),
            pl.BlockSpec((1, 1, d), sm),
            pl.BlockSpec((1, d, d), sm),
            pl.BlockSpec((d, g), lambda h, mb: (h, 0)),
            pl.BlockSpec((d, 1), lambda h, mb: (h, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, g, dv), lambda h, mb: (h, 0, 0)),
            pl.BlockSpec((1, 1, dv), sm),
            pl.BlockSpec((1, d, dv), sm),
            pl.BlockSpec((1, m2_rows, dv), mm),
            pl.BlockSpec((1, 1, 1), sm),
            pl.BlockSpec((1, 1, d), sm),
            pl.BlockSpec((1, d, d), sm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, g, dv), out_dtype),
            jax.ShapeDtypeStruct((bh, 1, dv), acc),
            jax.ShapeDtypeStruct((bh, d, dv), acc),
            jax.ShapeDtypeStruct((bh, nmb * m2_rows, dv), acc),
            jax.ShapeDtypeStruct((bh, 1, 1), acc),
            jax.ShapeDtypeStruct((bh, 1, d), acc),
            jax.ShapeDtypeStruct((bh, d, d), acc),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, dv), acc),
            pltpu.VMEM((g, 1), acc),
        ],
        input_output_aliases={3: 1, 4: 2, 5: 3, 6: 4, 7: 5, 8: 6},
        # the head axis follows the schedule's `grid` knob; the m-block
        # axis is the sequential m2 stream (carries acc/den scratch)
        compiler_params=compiler_params((grid, "arbitrary")),
        interpret=interpret,
        name=f"fastmax_decode_p{p}",
    )(qr, kr, vr, m0r, m1r, m2r, g0r, g1r, g2r, qt, kc, lv)

    o, m0n, m1n, m2n, g0n, g1n, g2n = outs
    o = o.reshape(b, hq, 1, dv)
    new_state = (
        m0n.reshape(b, hkv, dv),
        m1n.reshape(b, hkv, d, dv),
        m2n.reshape(b, hkv, d, d, dv) if p >= 2 else m2,
        g0n.reshape(b, hkv),
        g1n.reshape(b, hkv, d),
        g2n.reshape(b, hkv, d, d),
    )
    return o, new_state
