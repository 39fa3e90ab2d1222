"""Pallas TPU kernel: causal Fastmax attention via chunked prefix scan.

TPU-native redesign of the paper's masked Fastmax (DESIGN.md §2). The paper's
GPU code carries *per-row* prefix moments (O(N D^{p+1}) memory → the D× causal
wall-clock penalty they report in §3.1). Here the sequence is processed in
chunks of C tokens along a sequential grid axis; the running moments live in
VMEM scratch (O(D^{p+1}) bytes total), and every heavy op is an MXU matmul:

  intra-chunk:  S = Q K^T  (C×C),  f(S) masked, f(S)·V
  inter-chunk:  φ₂(Q) contracted against the moment carry, blocked over the
                first moment index so each step is a
                [G·C, bm·D] @ [bm·D, blk] matmul (bm chosen so bm·D ≈ 256-512)

Layout notes (TPU):
  * degree-2 moment scratch is [D·D, blk] (m-major) so both the update
    (T^T @ V) and the query contraction slice contiguous row blocks. The
    degree-2 features of a row block are built TRANSPOSED, [bm·D, rows],
    from k̂ᵀ/q̂ᵀ held in VMEM scratch (`_outer_rows`): the block's dynamic
    offset then falls on the sublane axis (one-row loads), which Mosaic
    lowers, where a dynamic lane slice of a value does not.
  * the VALUE-FEATURE axis of the carry (and of v / o / the emitted
    m-moments) is tiled into nb = Dv/blk independent column blocks
    (`pick_blk`): per-block scratch is D²·blk·4 bytes, so D = Dv = 128
    heads fit VMEM (blk = Dv ⇒ nb = 1 reproduces the unblocked schedule
    exactly). Each block redundantly recomputes the Dv-independent parts
    (QK^T, the denominator, the g-carry) and emits ITS slice of o and the
    m-moments — outputs slice cleanly because o = num/(den+eps) splits
    along Dv.
  * the validity mask is laid out [B·Hkv, 1, N] with (1, 1, C) blocks, so
    its block obeys the (8, 128) tiling rule (a [B·Hkv, N] layout with
    (1, C) blocks does not).
  * state blocks whose index is constant along the chunk axis (init state
    in, final state out) are single-buffered: at D = Dv = 128 each m2 block
    is 8 MB of VMEM.
  * grid = (B·Hkv, nb, N/C): head and Dv-block axes "parallel"
    (independent), chunk axis "arbitrary" (sequential — the scan carry).
  * GQA: Q arrives [B·Hkv, G, N, D]; the G query heads of a group are
    flattened into matmul rows so moments are computed ONCE per kv head
    (the paper's reference code recomputes them per q head).
  * fp32 accumulation regardless of input dtype (f64 in interpret tests).

Validated against `repro.kernels.ref.fastmax_ref` in interpret mode
(tests/test_kernels.py) across shapes, dtypes, p∈{1,2}, and GQA group sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import (FWD_BLK_BUDGET, VMEM_LIMIT_BYTES,
                                  pick_blk, pick_bm)

__all__ = ["fastmax_causal_pallas"]


def _poly(s, p):
    out = 1.0 + s
    if p >= 2:
        out = out + 0.5 * s * s
    return out


def _outer_rows(xt_ref, i, bm):
    """Rows [i·bm·D, (i+1)·bm·D) of the m-major degree-2 expansion of x,
    transposed: row a·D + b is x[:, i·bm + a] · x[:, b], shape [bm·D, R].
    `xt_ref` holds xᵀ [D, R] in VMEM, so the dynamic index `i` only ever
    selects sublane rows of a ref."""
    xt = xt_ref[...]
    return jnp.concatenate(
        [xt * xt_ref[pl.ds(i * bm + a, 1), :] for a in range(bm)], axis=0)


def _m_rows(i, rows):
    """pl.ds over row block `i` of `rows` rows of an m-major moment."""
    start = i * rows
    if rows % 8 == 0:
        start = pl.multiple_of(start, 8)
    return pl.ds(start, rows)


def _tn_dot(a, b, acc):
    """aᵀ @ b (contract the leading dims) with `acc` accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=acc)


def _nt_dot(a, b, acc):
    """a @ bᵀ (contract the trailing dims) with `acc` accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=acc)


def _state_spec(block, index_map):
    """BlockSpec of a state block whose index is constant along the chunk
    axis: one VMEM buffer instead of the default two."""
    return pl.BlockSpec(block, index_map, pipeline_mode=pl.Buffered(1))


def compiler_params(dimension_semantics):
    """Mosaic parameters shared by the fastmax kernels."""
    return pltpu.CompilerParams(dimension_semantics=tuple(dimension_semantics),
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _causal_kernel(
    q_ref,   # [1, G, C, D]
    k_ref,   # [1, C, D]
    v_ref,   # [1, C, Dv]
    w_ref,   # [1, 1, C]    validity mask (1=real token, 0=padding)
    *refs,   # [init-state inputs (has_init)] + o_ref +
    #          [state outputs (emit_state)] + 6 moment scratch buffers
    #          + q̂ᵀ/k̂ᵀ scratch
    p: int,
    bm: int,
    denom_eps: float,
    acc,
    emit_state: bool,
    has_init: bool,
):
    if has_init:
        # initial carry: tokens already folded before this call (context-
        # parallel shards / resumable prefill) — same layout as the emitted
        # state, read once at the first chunk
        (i0, i1, i2, j0, j1, j2) = refs[:6]
        refs = refs[6:]
    o_ref = refs[0]
    refs = refs[1:]
    if emit_state:
        # final-carry outputs, m-major m2 — the decode kernel's native layout
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
        if has_init:
            m0_s[...] = i0[0]
            m1_s[...] = i1[0]
            g0_s[...] = j0[0]
            g1_s[...] = j1[0]
            if p >= 2:
                m2_s[...] = i2[0]
                g2_s[...] = j2[0]
        else:
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

    # ---- inter-chunk: contract carry (strictly-previous chunks) with q ----
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
    static_argnames=("p", "chunk_size", "denom_eps", "interpret", "out_dtype",
                     "return_state", "blk", "bm", "grid"),
)
def fastmax_causal_pallas(
    q: jnp.ndarray,  # [B, Hq, N, D]  (pre-normalized q̂)
    k: jnp.ndarray,  # [B, Hkv, N, D] (pre-normalized k̂)
    v: jnp.ndarray,  # [B, Hkv, N, Dv]
    kv_mask: jnp.ndarray | None = None,  # [B, Hkv|1, N] validity (1=real)
    *,
    p: int = 2,
    chunk_size: int = 128,
    denom_eps: float = 1e-6,
    interpret: bool = False,
    out_dtype=None,
    return_state: bool = False,
    init_state=None,
    blk: int | None = None,
    bm: int | None = None,
    grid: str | None = None,
):
    """Causal fastmax. With `return_state=True` additionally returns the
    final moment carry as a tuple (m0, m1, m2, g0, g1, g2) with shapes
    ([B,Hkv,Dv], [B,Hkv,D,Dv], [B,Hkv,D,D,Dv], [B,Hkv], [B,Hkv,D],
    [B,Hkv,D,D]) in the accumulator dtype — emitted by the kernel itself
    (no second pass over k/v), ready for streaming decode.

    `init_state` seeds the scan carry with a moment tuple in that same
    layout (tokens already folded upstream: the earlier context-parallel
    shards of the sequence, or the already-prefilled prompt prefix). The
    scan then computes the EXACT causal output as if those tokens preceded
    this call's k/v — the associativity of the moment fold.

    `blk` is the Dv carry-block width (must divide Dv); None picks the
    largest divisor whose degree-2 scratch tuple fits `FWD_BLK_BUDGET`
    (nb = Dv/blk = 1 below 128×128 heads — the unblocked schedule).
    `bm` is the m-major row block (must divide D; None → `pick_bm`).
    `grid` selects the dimension semantics of the INDEPENDENT grid axes:
    "parallel" (None; megacore may split them) or "arbitrary" (sequential
    single-core sweep) — the autotuner's schedule knobs."""
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    g = hq // hkv
    if hq % hkv:
        raise ValueError(f"Hq={hq} % Hkv={hkv} != 0")
    out_dtype = out_dtype or q.dtype

    cs = min(chunk_size, max(8, n))
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
    has_init = init_state is not None
    kernel = functools.partial(_causal_kernel, p=p, bm=bm, denom_eps=denom_eps,
                               acc=acc, emit_state=return_state,
                               has_init=has_init)
    bh = b * hkv
    m2_rows = d * d if p >= 2 else 1
    sm = lambda h, b_, c: (h, 0, 0)       # noqa: E731 g-carry state blocks
    vb = lambda h, b_, c: (h, 0, b_)      # noqa: E731 Dv-blocked m-state
    in_specs = [
        pl.BlockSpec((1, g, cs, d), lambda h, b_, c: (h, 0, c, 0)),
        pl.BlockSpec((1, cs, d), lambda h, b_, c: (h, c, 0)),
        pl.BlockSpec((1, cs, blk), lambda h, b_, c: (h, c, b_)),
        pl.BlockSpec((1, 1, cs), lambda h, b_, c: (h, 0, c)),
    ]
    operands = [qp, kp, vp, w]
    state_specs = [
        _state_spec((1, 1, blk), vb),
        _state_spec((1, d, blk), vb),
        _state_spec((1, m2_rows, blk), vb),
        _state_spec((1, 1, 1), sm),
        _state_spec((1, 1, d), sm),
        _state_spec((1, d, d), sm),
    ]
    if has_init:
        i0, i1, i2, j0, j1, j2 = init_state
        operands += [
            i0.astype(acc).reshape(bh, 1, dv),
            i1.astype(acc).reshape(bh, d, dv),
            (i2.astype(acc).reshape(bh, d * d, dv) if p >= 2
             else jnp.zeros((bh, 1, dv), acc)),
            j0.astype(acc).reshape(bh, 1, 1),
            j1.astype(acc).reshape(bh, 1, d),
            j2.astype(acc).reshape(bh, d, d),
        ]
        in_specs += state_specs
    out_specs = [pl.BlockSpec((1, g, cs, blk), lambda h, b_, c: (h, 0, c, b_))]
    out_shape = [jax.ShapeDtypeStruct((bh, g, nc * cs, dv), out_dtype)]
    if return_state:
        out_specs += state_specs
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
        # nb must be sequential when emitting state: every Dv-block program
        # writes the SAME g-state output block (identical values), and
        # aliasing an output window across a "parallel" grid dim is
        # undefined on megacore (two cores would DMA it concurrently).
        # Without state outputs every block writes disjoint o slices, so
        # nb follows the schedule's `grid` knob.
        compiler_params=compiler_params(
            (par, "arbitrary" if return_state else par, "arbitrary")),
        interpret=interpret,
        name=f"fastmax_causal_p{p}",
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
