"""Pallas TPU kernel: noncausal (bidirectional) Fastmax attention.

Two-phase schedule (DESIGN.md §2):

  Phase A (moments): grid (B·Hkv, MB, NC). For each m-block of the degree-2
    moment, stream the key/value chunks along the sequential NC axis and
    accumulate the [bm·D, Dv] moment tile resident in VMEM (output-revisiting
    pattern — index map constant along NC, so the tile is flushed once per
    m-block). Degree-0/1 moments + denominators accumulate only on the
    mb==0 pass.

  Phase B (combine): grid (B·Hkv, NQ, MB). Per query block, accumulate the
    φ₂(Q)·m2 contraction across m-blocks in an fp32 scratch accumulator and
    divide by the (m-block-independent) denominator on the last step.

Used for encoder / cross-attention (whisper, chameleon image-prefix) and for
noncausal LRA-style classification. Everything is MXU matmuls; VMEM per step
is O(C·D + bm·D·Dv) — independent of N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fastmax_causal import (_outer_rows, _tn_dot,
                                          compiler_params)
from repro.kernels.tiling import pick_bm

__all__ = ["fastmax_noncausal_pallas"]


def _moment_kernel(k_ref, v_ref, w_ref,
                   m0_ref, m1_ref, m2_ref, g0_ref, g1_ref, g2_ref, kt_s,
                   *, p, bm, acc):
    mb, c = pl.program_id(1), pl.program_id(2)

    k = k_ref[0].astype(acc)
    v = v_ref[0].astype(acc)
    w = w_ref[0, 0].astype(acc)
    kw = k * w[:, None]
    vw = v * w[:, None]

    @pl.when(jnp.logical_and(mb == 0, c == 0))
    def _init_small():
        m0_ref[...] = jnp.zeros_like(m0_ref)
        m1_ref[...] = jnp.zeros_like(m1_ref)
        g0_ref[...] = jnp.zeros_like(g0_ref)
        g1_ref[...] = jnp.zeros_like(g1_ref)
        if p >= 2:
            g2_ref[...] = jnp.zeros_like(g2_ref)

    @pl.when(mb == 0)
    def _small():
        m0_ref[0] += jnp.sum(vw, axis=0, keepdims=True)
        m1_ref[0] += jnp.dot(kw.T, v, preferred_element_type=acc)
        g0_ref[0] += jnp.sum(w).reshape(1, 1)
        g1_ref[0] += jnp.sum(kw, axis=0, keepdims=True)
        if p >= 2:
            g2_ref[0] += jnp.dot(kw.T, k, preferred_element_type=acc)

    if p >= 2:
        @pl.when(c == 0)
        def _init_m2():
            m2_ref[...] = jnp.zeros_like(m2_ref)

        kt_s[...] = k.T
        t = _outer_rows(kt_s, mb, bm)                       # [bm*D, C]
        m2_ref[0] += jnp.dot(t, vw, preferred_element_type=acc)


def _combine_kernel(q_ref, m0_ref, m1_ref, m2_ref, g0_ref, g1_ref, g2_ref,
                    o_ref, acc_s, den_s, qt_s, *, p, bm, nmb, denom_eps,
                    acc):
    mb = pl.program_id(2)
    g, cq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    dv = m1_ref.shape[2]
    q = q_ref[0].astype(acc).reshape(g * cq, d)

    @pl.when(mb == 0)
    def _deg01():
        num = jnp.broadcast_to(m0_ref[0], (g * cq, dv)) + jnp.dot(
            q, m1_ref[0], preferred_element_type=acc)
        den = g0_ref[0, 0, 0] + jnp.dot(q, g1_ref[0, 0],
                                        preferred_element_type=acc)
        if p >= 2:
            den = den + 0.5 * jnp.sum(
                jnp.dot(q, g2_ref[0], preferred_element_type=acc) * q,
                axis=-1)
        acc_s[...] = num
        den_s[...] = den[:, None]

    if p >= 2:
        qt_s[...] = q.T
        y = _outer_rows(qt_s, mb, bm)                       # [bm*D, GC]
        acc_s[...] += 0.5 * _tn_dot(y, m2_ref[0], acc)

    @pl.when(mb == nmb - 1)
    def _emit():
        o = acc_s[...] / (den_s[...] + denom_eps)
        o_ref[0] = o.reshape(g, cq, dv).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("p", "chunk_size", "denom_eps", "interpret", "out_dtype",
                     "bm", "grid"),
)
def fastmax_noncausal_pallas(
    q: jnp.ndarray,  # [B, Hq, N, D]   (pre-normalized q̂)
    k: jnp.ndarray,  # [B, Hkv, M, D]  (pre-normalized k̂)
    v: jnp.ndarray,  # [B, Hkv, M, Dv]
    *,
    p: int = 2,
    chunk_size: int = 128,
    denom_eps: float = 1e-6,
    interpret: bool = False,
    out_dtype=None,
    bm: int | None = None,
    grid: str | None = None,
) -> jnp.ndarray:
    b, hq, n, d = q.shape
    hkv, m = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    out_dtype = out_dtype or q.dtype

    cs = min(chunk_size, max(8, m))
    nkc = -(-m // cs)
    padk = nkc * cs - m
    cq = min(chunk_size, max(8, n))
    nqc = -(-n // cq)
    padq = nqc * cq - n

    acc = jnp.promote_types(q.dtype, jnp.float32)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, padk), (0, 0))).reshape(
        b * hkv, nkc * cs, d)
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, padk), (0, 0))).reshape(
        b * hkv, nkc * cs, dv)
    w = jnp.pad(jnp.ones((b * hkv, 1, m), acc), ((0, 0), (0, 0), (0, padk)))
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, padq), (0, 0))).reshape(
        b, hkv, g, nqc * cq, d).reshape(b * hkv, g, nqc * cq, d)

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

    mom_kernel = functools.partial(_moment_kernel, p=p, bm=bm, acc=acc)
    m0, m1, m2, g0, g1, g2 = pl.pallas_call(
        mom_kernel,
        grid=(b * hkv, nmb, nkc),
        in_specs=[
            pl.BlockSpec((1, cs, d), lambda h, mb, c: (h, c, 0)),
            pl.BlockSpec((1, cs, dv), lambda h, mb, c: (h, c, 0)),
            pl.BlockSpec((1, 1, cs), lambda h, mb, c: (h, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, dv), lambda h, mb, c: (h, 0, 0)),
            pl.BlockSpec((1, d, dv), lambda h, mb, c: (h, 0, 0)),
            pl.BlockSpec((1, m2_rows, dv), lambda h, mb, c: (h, mb, 0)),
            pl.BlockSpec((1, 1, 1), lambda h, mb, c: (h, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h, mb, c: (h, 0, 0)),
            pl.BlockSpec((1, d, d), lambda h, mb, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, 1, dv), acc),
            jax.ShapeDtypeStruct((b * hkv, d, dv), acc),
            jax.ShapeDtypeStruct((b * hkv, nmb * m2_rows, dv), acc),
            jax.ShapeDtypeStruct((b * hkv, 1, 1), acc),
            jax.ShapeDtypeStruct((b * hkv, 1, d), acc),
            jax.ShapeDtypeStruct((b * hkv, d, d), acc),
        ],
        scratch_shapes=[pltpu.VMEM((d, cs), acc)],
        compiler_params=compiler_params((grid, "arbitrary", "arbitrary")),
        interpret=interpret,
        name=f"fastmax_moments_p{p}",
    )(kp, vp, w)

    comb_kernel = functools.partial(_combine_kernel, p=p, bm=bm, nmb=nmb,
                                    denom_eps=denom_eps, acc=acc)
    out = pl.pallas_call(
        comb_kernel,
        grid=(b * hkv, nqc, nmb),
        in_specs=[
            pl.BlockSpec((1, g, cq, d), lambda h, iq, mb: (h, 0, iq, 0)),
            pl.BlockSpec((1, 1, dv), lambda h, iq, mb: (h, 0, 0)),
            pl.BlockSpec((1, d, dv), lambda h, iq, mb: (h, 0, 0)),
            pl.BlockSpec((1, m2_rows, dv), lambda h, iq, mb: (h, mb, 0)),
            pl.BlockSpec((1, 1, 1), lambda h, iq, mb: (h, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda h, iq, mb: (h, 0, 0)),
            pl.BlockSpec((1, d, d), lambda h, iq, mb: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, cq, dv), lambda h, iq, mb: (h, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, nqc * cq, dv), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((g * cq, dv), acc),
            pltpu.VMEM((g * cq, 1), acc),
            pltpu.VMEM((d, g * cq), acc),
        ],
        compiler_params=compiler_params((grid, grid, "arbitrary")),
        interpret=interpret,
        name=f"fastmax_combine_p{p}",
    )(qp, m0, m1, m2, g0, g1, g2)

    out = out.reshape(b, hkv, g, nqc * cq, dv)[:, :, :, :n]
    return out.reshape(b, hq, n, dv)
