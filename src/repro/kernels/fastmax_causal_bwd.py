"""Pallas TPU kernel: fused causal Fastmax backward (paper §2.5).

The memory-reduced backward of the chunked causal forward
(`fastmax_causal.py`). The forward stores only (q, k, v, final moments);
this kernel walks the chunks in REVERSE along the sequential grid axis and,
per chunk, entirely in VMEM scratch:

  1. reconstructs the carry reversibly — moments are sums, so
     carry_before = carry_after − Δchunk (bit-exact: the subtraction mirrors
     the forward fold op-for-op),
  2. recomputes the chunk forward (inter-chunk moment contraction + exact
     intra-chunk f(QK^T) block) to get o, the output scale 1/(den+eps), and
     the denominator cotangent,
  3. emits dq (inter + intra terms), dk/dv (intra terms + the chain through
     this chunk's moment delta against the accumulated carry-cotangent),
  4. folds this chunk's moment-cotangent contributions into the carry-
     cotangent scratch for the chunks before it.

Dv-blocked carry (the 128×128-head enabler): the carry AND carry-cotangent
tuples are tiled over `nb = Dv/blk` value-feature column blocks along a
parallel grid axis — per-program scratch is two [D², blk] tuples
(~2·D²·blk·4 bytes) instead of two full [D², Dv] ones. The decomposition is
exact, not approximate: with u = do·deni restricted to a block and
sden_b = −Σ_j o_j u_j over the block's columns, EVERY backward term is
linear in (u_b, sden_b, and the per-block carry-cotangents they fold into),
while the nonlinear ingredients (den, 1/(den+eps), f'(QK^T), the mask) are
Dv-independent and recomputed identically per block from the redundantly
maintained g-carry. So

  dv  — slices: each block owns its Dv columns exactly;
  dq, dk — sum: the kernel emits per-block PARTIALS (leading nb axis, fp32
  accumulator dtype) and the wrapper reduces them in one XLA sum.

The same linearity is what makes the kernel shardable on Dv: a feature-TP
shard is just the blocks of its Dv slice, with the partial dq/dk psummed
across devices once per launch (`repro.kernels.sharded`).

Every heavy op is an MXU matmul; the degree-2 tensors stream in the same
m-major [bm·D, blk] blocks as the forward. Scratch is two moment tuples
(carry + carry-cotangent): O(D²·blk) bytes, independent of N — the §2.5
bound, now with zero HBM round-trips for the reconstruction AND a VMEM
footprint that fits production 128×128 heads (blk = pick_blk = 128 ⇒
nb = 1, with the scoped-VMEM limit raised to `VMEM_LIMIT_BYTES`). The
degree-2 features are built transposed from q̂ᵀ/k̂ᵀ scratch, the dk columns
of an m-row block are written as rows of dk̂ᵀ scratch, and dq accumulates
transposed, so every dynamic index is a sublane row (see
`fastmax_causal._outer_rows`).

Validated in interpret mode against the jnp `_causal_scan_cg_bwd` oracle
and oracle autodiff (tests/test_kernels.py) over p ∈ {1,2}, GQA group
sizes, dtypes, and forced block widths (blk=1 ≡ blk=Dv bit-comparisons).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fastmax_causal import (_m_rows, _nt_dot, _outer_rows,
                                          _poly, _state_spec, _tn_dot,
                                          compiler_params)
from repro.kernels.tiling import BWD_BLK_BUDGET, pick_blk, pick_bm

__all__ = ["fastmax_causal_bwd_pallas"]


def _causal_bwd_kernel(
    q_ref,    # [1, G, C, D]
    k_ref,    # [1, C, D]
    v_ref,    # [1, C, BLK]    this program's Dv column block
    w_ref,    # [1, 1, C]      validity mask (1=real token)
    do_ref,   # [1, G, C, BLK]
    fm0_ref,  # [1, 1, BLK]    final moments (read once, at the last chunk)
    fm1_ref,  # [1, D, BLK]
    fm2_ref,  # [1, M2R, BLK]  m-major
    fg0_ref,  # [1, 1, 1]      g-moments: full (Dv-independent)
    fg1_ref,  # [1, 1, D]
    fg2_ref,  # [1, D, D]
    dq_ref,   # [1, 1, G, C, D]  per-block PARTIAL (summed by the wrapper)
    dk_ref,   # [1, 1, C, D]     per-block PARTIAL
    dv_ref,   # [1, C, BLK]      exact slice
    *refs,    # [dstate outputs (return_dstate)] + 12 moment scratch
    #           buffers + q̂ᵀ/k̂ᵀ/uᵀ/dk̂ᵀ scratch
    p: int,
    bm: int,
    denom_eps: float,
    acc,
    return_dstate: bool,
):
    if return_dstate:
        # cotangent of the scan's INITIAL carry — the m-cotangents are exact
        # Dv-column slices, the g-cotangents per-block partials (leading nb
        # output axis, reduced by the wrapper). Context parallelism reads
        # this as dC_i: the gradient each earlier shard's carry receives.
        (dsm0, dsm1, dsm2, dsg0, dsg1, dsg2) = refs[:6]
        refs = refs[6:]
    # scratch: carry moments + carry-cotangent moments (Dv-block columns)
    (m0_s, m1_s, m2_s, g0_s, g1_s, g2_s,
     gm0_s, gm1_s, gm2_s, gg0_s, gg1_s, gg2_s, qt_s, kt_s, ut_s, dkt_s) = refs
    t = pl.program_id(2)   # reverse step: chunk = nc-1-t via the index maps
    g, cs, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    blk = v_ref.shape[2]
    gc = g * cs
    f32 = acc

    @pl.when(t == 0)
    def _init():
        m0_s[...] = fm0_ref[0]
        m1_s[...] = fm1_ref[0]
        g0_s[...] = fg0_ref[0]
        g1_s[...] = fg1_ref[0]
        gm0_s[...] = jnp.zeros_like(gm0_s)
        gm1_s[...] = jnp.zeros_like(gm1_s)
        gg0_s[...] = jnp.zeros_like(gg0_s)
        gg1_s[...] = jnp.zeros_like(gg1_s)
        if p >= 2:
            m2_s[...] = fm2_ref[0]
            g2_s[...] = fg2_ref[0]
            gm2_s[...] = jnp.zeros_like(gm2_s)
            gg2_s[...] = jnp.zeros_like(gg2_s)

    q = q_ref[0].astype(f32).reshape(gc, d)
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)
    w = w_ref[0, 0].astype(f32)
    do = do_ref[0].astype(f32).reshape(gc, blk)
    kw = k * w[:, None]
    vw = v * w[:, None]
    if p >= 2:
        qt_s[...] = q.T
        kt_s[...] = k.T

    # ---- 1. reversible carry: carry_before = carry_after − Δchunk --------
    # (op-for-op mirror of the forward fold, so the subtraction is exact;
    # the g-carry is Dv-independent and maintained redundantly per block)
    m0_s[...] -= jnp.sum(vw, axis=0, keepdims=True)
    m1_s[...] -= jnp.dot(kw.T, v, preferred_element_type=f32)
    g0_s[...] -= jnp.sum(w).reshape(1, 1)
    g1_s[...] -= jnp.sum(kw, axis=0, keepdims=True)
    if p >= 2:
        g2_s[...] -= jnp.dot(kw.T, k, preferred_element_type=f32)

        def mb_down(i, _):
            tt = _outer_rows(kt_s, i, bm)                    # [bm*D, C]
            m2_s[_m_rows(i, bm * d), :] -= jnp.dot(
                tt, vw, preferred_element_type=f32)
            return 0

        jax.lax.fori_loop(0, d // bm, mb_down, 0)

    # ---- 2. recompute the chunk forward against carry_before -------------
    # num: this block's Dv columns only; den: full (Dv-independent)
    num = jnp.broadcast_to(m0_s[...], (gc, blk)) + jnp.dot(
        q, m1_s[...], preferred_element_type=f32)
    # den/deni/sden stay [GC, 1] columns: Mosaic cannot relayout a 1-D
    # vector longer than one lane row into a column
    den = g0_s[...] + _nt_dot(q, g1_s[...], f32)
    if p >= 2:
        den = den + 0.5 * jnp.sum(
            jnp.dot(q, g2_s[...], preferred_element_type=f32) * q, axis=-1,
            keepdims=True)

        def mb_num(i, a):
            y = _outer_rows(qt_s, i, bm)                     # [bm*D, GC]
            z = m2_s[_m_rows(i, bm * d), :]
            return a + _tn_dot(y, z, f32)

        num = num + 0.5 * jax.lax.fori_loop(
            0, d // bm, mb_num, jnp.zeros((gc, blk), f32))

    s_qk = jnp.dot(q, k.T, preferred_element_type=f32)   # [GC, C]
    qpos = jax.lax.broadcasted_iota(jnp.int32, (gc, cs), 0) % cs
    kpos = jax.lax.broadcasted_iota(jnp.int32, (gc, cs), 1)
    mask = (qpos >= kpos).astype(f32) * w[None, :]
    fs = _poly(s_qk, p) * mask
    num = num + jnp.dot(fs, v, preferred_element_type=f32)
    den = den + jnp.sum(fs, axis=-1, keepdims=True)

    deni = 1.0 / (den + denom_eps)
    o = num * deni                         # this block's output columns
    u = do * deni                          # dL/dnum (block columns)
    sden = -jnp.sum(o * u, axis=-1, keepdims=True)  # block PARTIAL of dL/dden

    # ---- 3a. intra-chunk grads through the f(QK^T) block ------------------
    # ds decomposes additively over Dv blocks: u@v^T contracts only this
    # block's columns and sden is the block partial, so Σ_blocks ds == full
    fprime = (1.0 + s_qk) if p >= 2 else jnp.ones_like(s_qk)
    ds = (jnp.dot(u, v.T, preferred_element_type=f32)
          + sden) * fprime * mask
    dq = jnp.dot(ds, k, preferred_element_type=f32)      # [GC, D]
    dk = jnp.dot(ds.T, q, preferred_element_type=f32)    # [C, D]
    dvv = jnp.dot(fs.T, u, preferred_element_type=f32)   # [C, BLK]

    # ---- 3b. inter-chunk dq through the carry moments ---------------------
    dq += jnp.dot(u, m1_s[...].T, preferred_element_type=f32)
    dq += sden * g1_s[...]
    if p >= 2:
        dq += sden * jnp.dot(q, g2_s[...], preferred_element_type=f32)

        # m2 is symmetric in its two feature indices (row a·D+b == row
        # b·D+a), so Σ_b (u·m2[c·D+b]) q_b == Σ_b q_b (u·m2[b·D+c]): dqᵀ
        # accumulates whole [D, GC] terms, one m2 row block per feature b
        ut_s[...] = u.T

        # unrolled in Python: inside a fori_loop the TPU compiler rejects
        # this matmul (internal error in its MXU transpose folding)
        dqt = jnp.zeros((d, gc), f32)
        for b_ in range(d):
            dqt = dqt + qt_s[b_:b_ + 1, :] * jnp.dot(
                m2_s[b_ * d:(b_ + 1) * d, :], ut_s[...],
                preferred_element_type=f32)
        dq += dqt.T

    # ---- 3c. dk/dv through this chunk's moment delta (uses the carry-
    # cotangent accumulated from LATER chunks — before step 4 updates it) ---
    dk += w[:, None] * jnp.dot(v, gm1_s[...].T, preferred_element_type=f32)
    dk += w[:, None] * gg1_s[...]
    dvv += w[:, None] * jnp.broadcast_to(gm0_s[...], (cs, blk))
    dvv += w[:, None] * jnp.dot(k, gm1_s[...], preferred_element_type=f32)
    if p >= 2:
        dk += 2.0 * w[:, None] * jnp.dot(k, gg2_s[...],
                                         preferred_element_type=f32)

        def mb_dkv(i, dv_a):
            z = gm2_s[_m_rows(i, bm * d), :]                 # [bm*D, BLK]
            tt = _outer_rows(kt_s, i, bm)                    # [bm*D, C]
            dv_a = dv_a + _tn_dot(tt, z, f32)
            tmp = _nt_dot(z, vw, f32)                        # [bm*D, C]
            for a in range(bm):   # dk[:, i*bm + a] as a row of dkᵀ
                dkt_s[pl.ds(i * bm + a, 1), :] = 2.0 * jnp.sum(
                    tmp[a * d:(a + 1) * d] * kt_s[...], axis=0, keepdims=True)
            return dv_a

        dv2 = jax.lax.fori_loop(0, d // bm, mb_dkv,
                                jnp.zeros((cs, blk), f32))
        dk += dkt_s[...].T
        dvv += w[:, None] * dv2

    # ---- 4. fold this chunk's carry-cotangent for earlier chunks ----------
    # the gg-moments accumulate the block-PARTIAL sden, so the dk terms
    # they feed (step 3c) stay additively decomposed too
    gm0_s[...] += jnp.sum(u, axis=0, keepdims=True)
    gm1_s[...] += jnp.dot(q.T, u, preferred_element_type=f32)
    gg0_s[...] += jnp.sum(sden, axis=0, keepdims=True)
    gg1_s[...] += jnp.sum(sden * q, axis=0, keepdims=True)
    if p >= 2:
        gg2_s[...] += 0.5 * jnp.dot(q.T, q * sden,
                                    preferred_element_type=f32)

        def mb_gm2(i, _):
            y = _outer_rows(qt_s, i, bm)                     # [bm*D, GC]
            gm2_s[_m_rows(i, bm * d), :] += 0.5 * jnp.dot(
                y, u, preferred_element_type=f32)
            return 0

        jax.lax.fori_loop(0, d // bm, mb_gm2, 0)

    dq_ref[0, 0] = dq.reshape(g, cs, d).astype(dq_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dvv.astype(dv_ref.dtype)

    if return_dstate:
        nc = pl.num_programs(2)

        @pl.when(t == nc - 1)
        def _emit_dstate():
            # after folding chunk 0 (step 4 above) the carry-cotangent
            # scratch IS d(initial carry) — every local chunk's use of the
            # seeded moments has been chained through
            dsm0[0] = gm0_s[...]
            dsm1[0] = gm1_s[...]
            dsg0[0, 0] = gg0_s[...]
            dsg1[0, 0] = gg1_s[...]
            if p >= 2:
                dsm2[0] = gm2_s[...]
                dsg2[0, 0] = gg2_s[...]
            else:
                dsm2[0] = jnp.zeros_like(dsm2[0])
                dsg2[0, 0] = jnp.zeros_like(dsg2[0, 0])


@functools.partial(
    jax.jit,
    static_argnames=("p", "chunk_size", "denom_eps", "interpret", "blk",
                     "bm", "grid", "return_dstate"),
)
def fastmax_causal_bwd_pallas(
    q: jnp.ndarray,   # [B, Hq, N, D]   (pre-normalized q̂, as in the fwd)
    k: jnp.ndarray,   # [B, Hkv, N, D]
    v: jnp.ndarray,   # [B, Hkv, N, Dv]
    state: tuple,     # final moments: ([B,Hkv,Dv], [B,Hkv,D,Dv],
    #                   [B,Hkv,D,D,Dv], [B,Hkv], [B,Hkv,D], [B,Hkv,D,D])
    do: jnp.ndarray,  # [B, Hq, N, Dv]  output cotangent
    *,
    p: int = 2,
    chunk_size: int = 128,
    denom_eps: float = 1e-6,
    interpret: bool = False,
    blk: int | None = None,
    bm: int | None = None,
    grid: str | None = None,
    return_dstate: bool = False,
):
    """Returns (dq, dk, dv) in the input dtypes. With `return_dstate=True`
    additionally returns the cotangent of the scan's initial carry as a
    moment-layout tuple ([B,Hkv,Dv], [B,Hkv,D,Dv], [B,Hkv,D,D,Dv], [B,Hkv],
    [B,Hkv,D], [B,Hkv,D,D]) in the accumulator dtype. When the forward was
    seeded with an initial state (context-parallel shards), `state` must be
    that SEEDED forward's final carry; the reversible subtraction then
    reconstructs down to the seed and the emitted cotangent is exactly the
    gradient the seed — i.e. every earlier shard's moment delta — receives.

    `blk` is the Dv carry-block width (must divide Dv); None picks the
    largest lane-tileable divisor keeping BOTH degree-2 scratch tuples
    under `BWD_BLK_BUDGET` each (the smallest tileable one when none fits)
    — nb = Dv/blk = 1 (the unblocked schedule) through 128×128 heads.
    Feature-TP callers pass their LOCAL Dv shard; the emitted dq/dk are
    then the shard's partials (psummed once per launch by
    `repro.kernels.sharded`). `bm` (m-major row block, must
    divide D) and `grid` ("parallel"|"arbitrary" for the independent grid
    axes) are the autotuner's remaining schedule knobs; None keeps the
    untuned defaults.
    """
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    g = hq // hkv
    if hq % hkv:
        raise ValueError(f"Hq={hq} % Hkv={hkv} != 0")
    bh = b * hkv
    acc = jnp.promote_types(q.dtype, jnp.float32)

    cs = min(chunk_size, max(8, n))
    nc = -(-n // cs)
    pad = nc * cs - n
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b, hkv, g, nc * cs, d).reshape(bh, g, nc * cs, d)
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        bh, nc * cs, d)
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        bh, nc * cs, dv)
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
        b, hkv, g, nc * cs, dv).reshape(bh, g, nc * cs, dv)
    w = jnp.pad(jnp.ones((bh, 1, n), acc), ((0, 0), (0, 0), (0, pad)))

    m0, m1, m2, g0, g1, g2 = state
    m2_rows = d * d if p >= 2 else 1
    fm0 = m0.reshape(bh, 1, dv).astype(acc)
    fm1 = m1.reshape(bh, d, dv).astype(acc)
    fm2 = (m2.reshape(bh, d * d, dv).astype(acc) if p >= 2
           else jnp.zeros((bh, 1, dv), acc))
    fg0 = g0.reshape(bh, 1, 1).astype(acc)
    fg1 = g1.reshape(bh, 1, d).astype(acc)
    fg2 = g2.reshape(bh, d, d).astype(acc)

    if bm is None:
        bm = pick_bm(d)
    if d % bm:
        raise ValueError(f"bm={bm} must divide D={d}")
    if blk is None:
        blk = pick_blk(d, dv, BWD_BLK_BUDGET)
    if dv % blk:
        raise ValueError(f"blk={blk} must divide Dv={dv}")
    if grid is None:
        grid = "parallel"
    if grid not in ("parallel", "arbitrary"):
        raise ValueError(f"grid={grid!r}; expected 'parallel'|'arbitrary'")
    par = "parallel" if grid == "parallel" else "arbitrary"
    nb = dv // blk
    gc = g * cs
    kernel = functools.partial(_causal_bwd_kernel, p=p, bm=bm,
                               denom_eps=denom_eps, acc=acc,
                               return_dstate=return_dstate)
    rev = lambda h, b_, t: (h, nc - 1 - t, 0)        # noqa: E731 rev chunks
    revb = lambda h, b_, t: (h, nc - 1 - t, b_)      # noqa: E731 + Dv block
    revq = lambda h, b_, t: (h, 0, nc - 1 - t, 0)    # noqa: E731
    revqb = lambda h, b_, t: (h, 0, nc - 1 - t, b_)  # noqa: E731
    vb = lambda h, b_, t: (h, 0, b_)                 # noqa: E731 m-state
    sm = lambda h, b_, t: (h, 0, 0)                  # noqa: E731 g-state
    # dq/dk come back as per-Dv-block fp32 partials (leading nb axis) and
    # are reduced here: every backward term is linear in the block-local
    # cotangents, so the sum over blocks is the exact full gradient
    out_specs = [
        pl.BlockSpec((1, 1, g, cs, d),
                     lambda h, b_, t: (h, b_, 0, nc - 1 - t, 0)),
        pl.BlockSpec((1, 1, cs, d),
                     lambda h, b_, t: (h, b_, nc - 1 - t, 0)),
        pl.BlockSpec((1, cs, blk), revb),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((bh, nb, g, nc * cs, d), acc),
        jax.ShapeDtypeStruct((bh, nb, nc * cs, d), acc),
        jax.ShapeDtypeStruct((bh, nc * cs, dv), v.dtype),
    ]
    if return_dstate:
        # m-cotangents slice cleanly over Dv (vb); g-cotangents are built
        # from the block-partial sden, so they carry a leading nb axis and
        # are reduced below — the same partial/slice split as dq/dk vs dv
        nbm = lambda h, b_, t: (h, b_, 0, 0)         # noqa: E731
        out_specs += [
            _state_spec((1, 1, blk), vb),
            _state_spec((1, d, blk), vb),
            _state_spec((1, m2_rows, blk), vb),
            _state_spec((1, 1, 1, 1), nbm),
            _state_spec((1, 1, 1, d), nbm),
            _state_spec((1, 1, d, d), nbm),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((bh, 1, dv), acc),
            jax.ShapeDtypeStruct((bh, d, dv), acc),
            jax.ShapeDtypeStruct((bh, m2_rows, dv), acc),
            jax.ShapeDtypeStruct((bh, nb, 1, 1), acc),
            jax.ShapeDtypeStruct((bh, nb, 1, d), acc),
            jax.ShapeDtypeStruct((bh, nb, d, d), acc),
        ]
    outs = pl.pallas_call(
        kernel,
        grid=(bh, nb, nc),
        in_specs=[
            pl.BlockSpec((1, g, cs, d), revq),
            pl.BlockSpec((1, cs, d), rev),
            pl.BlockSpec((1, cs, blk), revb),
            pl.BlockSpec((1, 1, cs), lambda h, b_, t: (h, 0, nc - 1 - t)),
            pl.BlockSpec((1, g, cs, blk), revqb),
            _state_spec((1, 1, blk), vb),
            _state_spec((1, d, blk), vb),
            _state_spec((1, m2_rows, blk), vb),
            _state_spec((1, 1, 1), sm),
            _state_spec((1, 1, d), sm),
            _state_spec((1, d, d), sm),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((1, blk), acc),
            pltpu.VMEM((d, blk), acc),
            pltpu.VMEM((m2_rows, blk), acc),
            pltpu.VMEM((1, 1), acc),
            pltpu.VMEM((1, d), acc),
            pltpu.VMEM((d, d), acc),
            pltpu.VMEM((1, blk), acc),
            pltpu.VMEM((d, blk), acc),
            pltpu.VMEM((m2_rows, blk), acc),
            pltpu.VMEM((1, 1), acc),
            pltpu.VMEM((1, d), acc),
            pltpu.VMEM((d, d), acc),
            pltpu.VMEM((d, gc), acc),
            pltpu.VMEM((d, cs), acc),
            pltpu.VMEM((blk, gc), acc),
            pltpu.VMEM((d, cs), acc),
        ],
        compiler_params=compiler_params((par, par, "arbitrary")),
        interpret=interpret,
        name=f"fastmax_causal_bwd_p{p}",
    )(qp, kp, vp, w, dop, fm0, fm1, fm2, fg0, fg1, fg2)

    dq_p, dk_p, dvv = outs[:3]
    dq = jnp.sum(dq_p, axis=1).astype(q.dtype)
    dk = jnp.sum(dk_p, axis=1).astype(k.dtype)
    dq = dq.reshape(b, hkv, g, nc * cs, d)[:, :, :, :n].reshape(b, hq, n, d)
    dk = dk.reshape(b, hkv, nc * cs, d)[:, :, :n]
    dvv = dvv.reshape(b, hkv, nc * cs, dv)[:, :, :n]
    if not return_dstate:
        return dq, dk, dvv
    dsm0, dsm1, dsm2, dsg0, dsg1, dsg2 = outs[3:]
    dstate = (
        dsm0.reshape(b, hkv, dv),
        dsm1.reshape(b, hkv, d, dv),
        (dsm2.reshape(b, hkv, d, d, dv) if p >= 2
         else jnp.zeros((b, hkv, d, d, dv), acc)),
        jnp.sum(dsg0, axis=1).reshape(b, hkv),
        jnp.sum(dsg1, axis=1).reshape(b, hkv, d),
        jnp.sum(dsg2, axis=1).reshape(b, hkv, d, d),
    )
    return dq, dk, dvv, dstate
