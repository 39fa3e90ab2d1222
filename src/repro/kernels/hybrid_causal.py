"""Pallas TPU kernel: hybrid near/far-field causal attention.

One launch per chunk, same chunked prefix-scan schedule as
`fastmax_causal.py` — the far field is the identical reversible moment
carry (VMEM scratch, m-major degree-2 block, Dv column blocks) — plus
the near field: an exact (exp - f_p) correction over the width-w causal
band, computed from the score blocks the scan already touches. Because
the effective band is clamped to one chunk (w_eff = min(window, C)), the
band only ever reaches the CURRENT chunk's keys and the PREVIOUS
chunk's, so the kernel adds exactly two extra inputs: the previous
chunk's (k, v, validity) blocks, selected by an index map at c-1 and
nulled at c == 0.

The correction form keeps the moment leg untouched: the band adds
(exp(s) - f_p(s)) on top of the f_p(s) the intra-chunk/moment paths
already contribute, so numerator and denominator stay one sum and w=0
reproduces fastmax exactly.

Forward-only (+ emitted final carry): the trainable path's backward is
the jnp §2.5 reverse scan extended with band residuals
(`repro.core.hybrid.hybrid_bwd_scan`), seeded by this kernel's emitted
state — see `kernels/ops.hybrid`.

Validated against `repro.core.hybrid.hybrid_attention_ref` in interpret
mode (tests/test_hybrid.py) in f64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fastmax_causal import (_m_rows, _outer_rows, _poly,
                                          _state_spec, _tn_dot,
                                          compiler_params)
from repro.kernels.tiling import FWD_BLK_BUDGET, pick_blk, pick_bm

__all__ = ["hybrid_causal_pallas"]


def _hybrid_kernel(
    q_ref,    # [1, G, C, D]
    k_ref,    # [1, C, D]
    v_ref,    # [1, C, Dv-block]
    w_ref,    # [1, 1, C]    validity mask (1=real token, 0=padding)
    kp_ref,   # [1, C, D]    previous chunk's keys   (block c-1; junk at c=0)
    vp_ref,   # [1, C, Dv-block] previous chunk's values
    wp_ref,   # [1, 1, C]    previous chunk's validity
    *refs,    # o_ref + [state outputs (emit_state)] + 6 moment scratch
    #           + q̂ᵀ/k̂ᵀ scratch
    p: int,
    bm: int,
    w_eff: int,
    denom_eps: float,
    acc,
    emit_state: bool,
):
    o_ref = refs[0]
    refs = refs[1:]
    if emit_state:
        (m0o, m1o, m2o, g0o, g1o, g2o) = refs[:6]
        refs = refs[6:]
    m0_s, m1_s, m2_s, g0_s, g1_s, g2_s, qt_s, kt_s = refs
    c = pl.program_id(2)
    nc = pl.num_programs(2)
    g, cs, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    dv = v_ref.shape[2]

    f32 = acc
    @pl.when(c == 0)
    def _init():
        m0_s[...] = jnp.zeros_like(m0_s)
        m1_s[...] = jnp.zeros_like(m1_s)
        g0_s[...] = jnp.zeros_like(g0_s)
        g1_s[...] = jnp.zeros_like(g1_s)
        if p >= 2:
            m2_s[...] = jnp.zeros_like(m2_s)
            g2_s[...] = jnp.zeros_like(g2_s)

    q = q_ref[0].astype(f32).reshape(g * cs, d)   # [GC, D]
    k = k_ref[0].astype(f32)                      # [C, D]
    v = v_ref[0].astype(f32)                      # [C, Dv]
    w = w_ref[0, 0].astype(f32)                   # [C]

    # ---- far field: contract carry (strictly-previous chunks) with q ----
    num = jnp.broadcast_to(m0_s[...], (g * cs, dv)) + jnp.dot(
        q, m1_s[...], preferred_element_type=f32
    )
    den = g0_s[0, 0] + jnp.dot(q, g1_s[0], preferred_element_type=f32)
    if p >= 2:
        den = den + 0.5 * jnp.sum(
            jnp.dot(q, g2_s[...], preferred_element_type=f32) * q,
            axis=-1,
        )

        qt_s[...] = q.T

        def mb_step(i, acc_):
            y = _outer_rows(qt_s, i, bm)                    # [bm*D, GC]
            z = m2_s[_m_rows(i, bm * d), :]                 # [bm*D, Dv]
            return acc_ + _tn_dot(y, z, f32)

        num = num + 0.5 * jax.lax.fori_loop(
            0, d // bm, mb_step, jnp.zeros((g * cs, dv), f32)
        )

    # ---- intra-chunk: exact causal block through f(QK^T) ----
    s = jnp.dot(q, k.T, preferred_element_type=f32)  # [GC, C]
    fs = _poly(s, p)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (g * cs, cs), 0) % cs
    kpos = jax.lax.broadcasted_iota(jnp.int32, (g * cs, cs), 1)
    fs = jnp.where(qpos >= kpos, fs, 0.0) * w[None, :]
    num = num + jnp.dot(fs, v, preferred_element_type=f32)
    den = den + jnp.sum(fs, axis=-1)

    # ---- near field: (exp - f_p) over the width-w_eff causal band ----
    if w_eff > 0:
        intra = (qpos >= kpos) & (qpos - kpos < w_eff)
        corr = jnp.where(intra, jnp.exp(s) - _poly(s, p), 0.0) * w[None, :]
        num = num + jnp.dot(corr, v, preferred_element_type=f32)
        den = den + jnp.sum(corr, axis=-1)
        # previous chunk's keys: distance = qpos + C - kpos, gated at c==0
        kprev = kp_ref[0].astype(f32)
        vprev = vp_ref[0].astype(f32)
        wprev = wp_ref[0, 0].astype(f32) * jnp.where(c > 0, 1.0, 0.0)
        sp = jnp.dot(q, kprev.T, preferred_element_type=f32)
        pband = (qpos + cs - kpos) < w_eff
        corr_p = jnp.where(pband, jnp.exp(sp) - _poly(sp, p), 0.0)
        corr_p = corr_p * wprev[None, :]
        num = num + jnp.dot(corr_p, vprev, preferred_element_type=f32)
        den = den + jnp.sum(corr_p, axis=-1)

    o = num / (den + denom_eps)[:, None]
    o_ref[0] = o.reshape(g, cs, dv).astype(o_ref.dtype)

    # ---- fold this chunk into the carry ----
    kw = k * w[:, None]
    vw = v * w[:, None]
    m0_s[...] += jnp.sum(vw, axis=0, keepdims=True)
    m1_s[...] += jnp.dot(kw.T, v, preferred_element_type=f32)
    g0_s[...] += jnp.sum(w).reshape(1, 1)
    g1_s[...] += jnp.sum(kw, axis=0, keepdims=True)
    if p >= 2:
        g2_s[...] += jnp.dot(kw.T, k, preferred_element_type=f32)

        kt_s[...] = k.T

        def mb_up(i, _):
            t = _outer_rows(kt_s, i, bm)                    # [bm*D, C]
            m2_s[_m_rows(i, bm * d), :] += jnp.dot(
                t, vw, preferred_element_type=f32
            )
            return 0

        jax.lax.fori_loop(0, d // bm, mb_up, 0)

    if emit_state:
        @pl.when(c == nc - 1)
        def _emit_state():
            m0o[0] = m0_s[...]
            m1o[0] = m1_s[...]
            g0o[0] = g0_s[...]
            g1o[0] = g1_s[...]
            if p >= 2:
                m2o[0] = m2_s[...]
                g2o[0] = g2_s[...]
            else:
                m2o[0] = jnp.zeros_like(m2o[0])
                g2o[0] = jnp.zeros_like(g2o[0])


@functools.partial(
    jax.jit,
    static_argnames=("p", "window", "chunk_size", "denom_eps", "interpret",
                     "out_dtype", "return_state", "blk", "bm", "grid"),
)
def hybrid_causal_pallas(
    q: jnp.ndarray,  # [B, Hq, N, D]  (pre-normalized q̂)
    k: jnp.ndarray,  # [B, Hkv, N, D] (pre-normalized k̂)
    v: jnp.ndarray,  # [B, Hkv, N, Dv]
    kv_mask: jnp.ndarray | None = None,  # [B, Hkv|1, N] validity (1=real)
    *,
    p: int = 2,
    window: int = 64,
    chunk_size: int = 128,
    denom_eps: float = 1e-6,
    interpret: bool = False,
    out_dtype=None,
    return_state: bool = False,
    blk: int | None = None,
    bm: int | None = None,
    grid: str | None = None,
):
    """Hybrid causal forward. `window` is clamped to the chunk
    (w_eff = min(window, C)); at w_eff == 0 this IS fastmax and the call
    delegates to `fastmax_causal_pallas` for bitwise parity. With
    `return_state=True` additionally returns the final MOMENT carry
    (m0, m1, m2, g0, g1, g2) in the fastmax layout — the band holds no
    carry (it is recomputed from k/v wherever needed), so the state
    shape is identical to fastmax's. Schedule knobs (blk/bm/grid) as in
    `fastmax_causal_pallas`."""
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    g = hq // hkv
    if hq % hkv:
        raise ValueError(f"Hq={hq} % Hkv={hkv} != 0")
    out_dtype = out_dtype or q.dtype

    cs = min(chunk_size, max(8, n))
    w_eff = max(0, min(window, cs))
    if w_eff == 0:
        from repro.kernels.fastmax_causal import fastmax_causal_pallas
        return fastmax_causal_pallas(
            q, k, v, kv_mask, p=p, chunk_size=chunk_size,
            denom_eps=denom_eps, interpret=interpret, out_dtype=out_dtype,
            return_state=return_state, blk=blk, bm=bm, grid=grid)
    nc = -(-n // cs)
    pad = nc * cs - n
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b, hkv, g, nc * cs, d).reshape(b * hkv, g, nc * cs, d)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b * hkv, nc * cs, d)
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b * hkv, nc * cs, dv)
    acc = jnp.promote_types(q.dtype, jnp.float32)
    if kv_mask is None:
        w = jnp.ones((b, hkv, n), acc)
    else:
        w = jnp.broadcast_to(kv_mask.astype(acc), (b, hkv, n))
    w = jnp.pad(w, ((0, 0), (0, 0), (0, pad))).reshape(b * hkv, 1, nc * cs)

    if bm is None:
        bm = pick_bm(d)
    if d % bm:
        raise ValueError(f"bm={bm} must divide D={d}")
    if blk is None:
        blk = pick_blk(d, dv, FWD_BLK_BUDGET)
    if dv % blk:
        raise ValueError(f"blk={blk} must divide Dv={dv}")
    if grid is None:
        grid = "parallel"
    if grid not in ("parallel", "arbitrary"):
        raise ValueError(f"grid={grid!r}; expected 'parallel'|'arbitrary'")
    par = "parallel" if grid == "parallel" else "arbitrary"
    nb = dv // blk
    kernel = functools.partial(_hybrid_kernel, p=p, bm=bm, w_eff=w_eff,
                               denom_eps=denom_eps, acc=acc,
                               emit_state=return_state)
    bh = b * hkv
    m2_rows = d * d if p >= 2 else 1
    sm = lambda h, b_, c: (h, 0, 0)       # noqa: E731 g-carry state blocks
    vb = lambda h, b_, c: (h, 0, b_)      # noqa: E731 Dv-blocked m-state
    # previous-chunk blocks: index map pins chunk c-1 (clamped at 0; the
    # kernel nulls the c == 0 contribution via the validity gate)
    pc = lambda h, b_, c: (h, jnp.maximum(c - 1, 0), 0)   # noqa: E731
    pv = lambda h, b_, c: (h, jnp.maximum(c - 1, 0), b_)  # noqa: E731
    pw = lambda h, b_, c: (h, 0, jnp.maximum(c - 1, 0))   # noqa: E731
    in_specs = [
        pl.BlockSpec((1, g, cs, d), lambda h, b_, c: (h, 0, c, 0)),
        pl.BlockSpec((1, cs, d), lambda h, b_, c: (h, c, 0)),
        pl.BlockSpec((1, cs, blk), lambda h, b_, c: (h, c, b_)),
        pl.BlockSpec((1, 1, cs), lambda h, b_, c: (h, 0, c)),
        pl.BlockSpec((1, cs, d), pc),
        pl.BlockSpec((1, cs, blk), pv),
        pl.BlockSpec((1, 1, cs), pw),
    ]
    operands = [qp, kp, vp, w, kp, vp, w]
    out_specs = [pl.BlockSpec((1, g, cs, blk), lambda h, b_, c: (h, 0, c, b_))]
    out_shape = [jax.ShapeDtypeStruct((bh, g, nc * cs, dv), out_dtype)]
    if return_state:
        out_specs += [
            _state_spec((1, 1, blk), vb),
            _state_spec((1, d, blk), vb),
            _state_spec((1, m2_rows, blk), vb),
            _state_spec((1, 1, 1), sm),
            _state_spec((1, 1, d), sm),
            _state_spec((1, d, d), sm),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((bh, 1, dv), acc),
            jax.ShapeDtypeStruct((bh, d, dv), acc),
            jax.ShapeDtypeStruct((bh, m2_rows, dv), acc),
            jax.ShapeDtypeStruct((bh, 1, 1), acc),
            jax.ShapeDtypeStruct((bh, 1, d), acc),
            jax.ShapeDtypeStruct((bh, d, d), acc),
        ]
    outs = pl.pallas_call(
        kernel,
        grid=(bh, nb, nc),
        in_specs=in_specs,
        out_specs=out_specs if return_state else out_specs[0],
        out_shape=out_shape if return_state else out_shape[0],
        scratch_shapes=[
            pltpu.VMEM((1, blk), acc),
            pltpu.VMEM((d, blk), acc),
            pltpu.VMEM((d * d if p >= 2 else 1, blk), acc),
            pltpu.VMEM((1, 1), acc),
            pltpu.VMEM((1, d), acc),
            pltpu.VMEM((d, d), acc),
            pltpu.VMEM((d, g * cs), acc),
            pltpu.VMEM((d, cs), acc),
        ],
        # nb sequential when emitting state, as in fastmax_causal (the
        # g-state output block is shared across Dv-block programs)
        compiler_params=compiler_params(
            (par, "arbitrary" if return_state else par, "arbitrary")),
        interpret=interpret,
        name=f"hybrid_causal_p{p}_w{w_eff}",
    )(*operands)
    if not return_state:
        outs = [outs]
    out = outs[0].reshape(b, hkv, g, nc * cs, dv)[:, :, :, :n]
    out = out.reshape(b, hq, n, dv)
    if not return_state:
        return out
    m0, m1, m2, g0, g1, g2 = outs[1:]
    state = (
        m0.reshape(b, hkv, dv),
        m1.reshape(b, hkv, d, dv),
        (m2.reshape(b, hkv, d, d, dv) if p >= 2
         else jnp.zeros((b, hkv, d, d, dv), acc)),
        g0.reshape(b, hkv),
        g1.reshape(b, hkv, d),
        g2.reshape(b, hkv, d, d),
    )
    return out, state
