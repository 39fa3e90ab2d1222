"""Pallas kernels vs pure-jnp oracle, interpret mode (same code Mosaic would
compile on TPU), swept over shapes / dtypes / p / GQA group sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_fastmax_state
from repro.core.ref import normalize_qk
from repro.kernels.ops import (fastmax, fastmax_decode,
                               fastmax_prefill_kernel)
from repro.kernels.ref import fastmax_decode_ref, fastmax_ref

jax.config.update("jax_enable_x64", True)

pytestmark = pytest.mark.kernels


def mk(rng, b, hq, hkv, n, d, dv, dtype):
    q = normalize_qk(jnp.asarray(rng.normal(size=(b, hq, n, d)), dtype))
    k = normalize_qk(jnp.asarray(rng.normal(size=(b, hkv, n, d)), dtype))
    v = jnp.asarray(rng.normal(size=(b, hkv, n, dv)), dtype)
    return q, k, v


SHAPES = [
    (1, 2, 1, 32, 8, 8),     # GQA g=2
    (2, 4, 2, 100, 16, 16),  # padding (100 -> 112 at cs=16)
    (1, 8, 2, 64, 8, 8),     # g=4
    (1, 4, 4, 48, 4, 4),     # MHA
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("p", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_kernel_matches_oracle_f64(shape, p, causal):
    rng = np.random.default_rng(hash((shape, p, causal)) % 2**31)
    q, k, v = mk(rng, *shape, jnp.float64)
    ref = fastmax_ref(q, k, v, p=p, causal=causal)
    out = fastmax(q, k, v, p=p, causal=causal, chunk_size=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-3),
                                       (jnp.bfloat16, 1e-1)])
def test_kernel_low_precision(dtype, tol):
    """fp32/bf16 inputs accumulate in fp32 — p=2 only (safe denominator)."""
    rng = np.random.default_rng(11)
    q, k, v = mk(rng, 1, 4, 2, 64, 8, 8, dtype)
    ref = fastmax_ref(q.astype(jnp.float64), k.astype(jnp.float64),
                      v.astype(jnp.float64), p=2, causal=True)
    out = fastmax(q, k, v, p=2, causal=True, chunk_size=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float64), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("p", [1, 2])
def test_decode_kernel_stream(p):
    rng = np.random.default_rng(12)
    B, Hq, Hkv, D, Dv = 2, 4, 2, 8, 8
    state = tuple(jax.tree.map(lambda x: x.astype(jnp.float64),
                               init_fastmax_state(B, Hkv, D, Dv, p=p)))
    for _ in range(4):
        q, k, v = mk(rng, B, Hq, Hkv, 1, D, Dv, jnp.float64)
        o_ref, st_ref = fastmax_decode_ref(q, k, v, state, p=p)
        o, st = fastmax_decode(q, k, v, state, p=p, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   rtol=1e-9, atol=1e-9)
        for a, b in zip(st, st_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-9, atol=1e-9)
        state = st


@pytest.mark.parametrize("mask", [(1, 1, 1), (1, 0, 1), (0, 0, 0)],
                         ids=["all_live", "mixed", "all_dead"])
@pytest.mark.parametrize("p", [1, 2])
def test_decode_kernel_live_mask(p, mask):
    """A slot that is not live keeps its six moment leaves bit for bit; a
    live slot's state and output rows equal the unmasked kernel's."""
    from repro.kernels.fastmax_decode import fastmax_decode_pallas
    rng = np.random.default_rng(14)
    B, Hq, Hkv, D, Dv = 3, 4, 2, 8, 8
    state = tuple(init_fastmax_state(B, Hkv, D, Dv, p=p))
    for _ in range(2):      # a state that is not all zeros
        q, k, v = mk(rng, B, Hq, Hkv, 1, D, Dv, jnp.float32)
        _, state = fastmax_decode_pallas(q, k, v, state, p=p, bm=4,
                                         interpret=True)
    q, k, v = mk(rng, B, Hq, Hkv, 1, D, Dv, jnp.float32)
    live = np.asarray(mask, bool)
    o_all, st_all = fastmax_decode_pallas(q, k, v, state, p=p, bm=4,
                                          interpret=True)
    o, st = fastmax_decode_pallas(q, k, v, state, jnp.asarray(live), p=p,
                                  bm=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(o)[live],
                                  np.asarray(o_all)[live])
    assert len(st) == 6
    for new, old, unmasked in zip(st, state, st_all):
        new, old, unmasked = map(np.asarray, (new, old, unmasked))
        np.testing.assert_array_equal(new[live], unmasked[live])
        np.testing.assert_array_equal(new[~live], old[~live])


def test_kernel_gradient_matches_chunked():
    """Kernel fwd pairs with the §2.5 reversible backward."""
    import repro.core.fastmax as fm
    rng = np.random.default_rng(13)
    q, k, v = mk(rng, 1, 2, 1, 40, 8, 8, jnp.float64)

    def loss_k(q, k, v):
        return jnp.sum(jnp.sin(fastmax(q, k, v, p=2, causal=True,
                                       chunk_size=16, interpret=True)))

    def loss_j(q, k, v):
        return jnp.sum(jnp.sin(fm.fastmax_causal_chunked(
            q, k, v, p=2, chunk_size=16, custom_grad=False)))

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gj = jax.grad(loss_j, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("shape", [(1, 2, 1, 40, 8, 8),   # GQA g=2
                                   (1, 4, 2, 33, 8, 8),   # padding 33->48
                                   (1, 8, 2, 64, 8, 16)])  # g=4, Dv != D
@pytest.mark.parametrize("p", [1, 2])
def test_pallas_bwd_matches_jnp_bwd_f64(shape, p):
    """Fused Pallas backward == jnp §2.5 chunked reverse scan (the oracle
    it replaces on the hot path)."""
    import repro.core.fastmax as fm
    rng = np.random.default_rng(hash((shape, p)) % 2**31)
    q, k, v = mk(rng, *shape, jnp.float64)

    def loss_k(q, k, v):
        return jnp.sum(jnp.sin(fastmax(q, k, v, p=p, causal=True,
                                       chunk_size=16, interpret=True)))

    def loss_j(q, k, v):
        return jnp.sum(jnp.sin(fm.fastmax_causal_chunked(
            q, k, v, p=p, chunk_size=16, custom_grad=True)))

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gj = jax.grad(loss_j, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-7, atol=1e-9)


@pytest.mark.parametrize("p", [1, 2])
def test_noncausal_kernel_grads_match_jnp(p):
    """The noncausal kernel op is differentiable: its custom_vjp pairs the
    two-phase Pallas forward with autodiff of the jnp moment path (encoder
    attention trains through the kernel route, no forward reroute)."""
    import repro.core.fastmax as fm
    rng = np.random.default_rng(17 + p)
    q, k, v = mk(rng, 1, 4, 2, 33, 8, 8, jnp.float64)

    def loss_k(q, k, v):
        return jnp.sum(jnp.sin(fastmax(q, k, v, p=p, causal=False,
                                       chunk_size=16, interpret=True)))

    def loss_j(q, k, v):
        return jnp.sum(jnp.sin(fm.fastmax_noncausal(q, k, v, p=p,
                                                    chunk_size=16)))

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gj = jax.grad(loss_j, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-9, atol=1e-11)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("p", [1, 2])
def test_pallas_bwd_low_precision_vs_oracle_autodiff(dtype, tol, p):
    """Low-precision inputs, fp32 accumulation: Pallas backward vs plain
    autodiff through the chunked scan, both evaluated on the same inputs.
    The 1e-5 f32 rel-err bound is the PR acceptance criterion."""
    import repro.core.fastmax as fm
    rng = np.random.default_rng(17 + p)
    q, k, v = mk(rng, 1, 4, 2, 48, 8, 8, dtype)

    def loss_k(q, k, v):
        return jnp.sum(fastmax(q, k, v, p=p, causal=True, chunk_size=16,
                               interpret=True))

    def loss_o(q, k, v):
        return jnp.sum(fm.fastmax_causal_chunked(
            q, k, v, p=p, chunk_size=16, custom_grad=False))

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    go = jax.grad(loss_o, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, go):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        rel = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)
        assert rel <= tol, f"rel err {rel} > {tol}"


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 5e-2)])
def test_blocked_bwd_128x128_parity(monkeypatch, dtype, tol):
    """The tentpole shape: D = Dv = 128, p = 2, GQA. The auto-picked Dv
    carry block is the one width the TPU can tile there (blk = Dv = 128,
    nb = 1: two [D², 128] scratch tuples under the raised VMEM limit),
    and the fused backward matches the jnp §2.5 reverse-scan oracle on
    the SAME kernel-emitted residual."""
    from repro.kernels import ops
    from repro.kernels.tiling import BWD_BLK_BUDGET, pick_blk

    d = dv = 128
    assert pick_blk(d, dv, BWD_BLK_BUDGET) == dv  # lane-tileable block
    rng = np.random.default_rng(41)
    q, k, v = mk(rng, 1, 2, 1, 64, d, dv, dtype)
    do = jnp.asarray(rng.normal(size=(1, 2, 64, dv)), dtype)
    _, res = ops._fc_fwd(q, k, v, 2, 32, 1e-6, True, None, None)
    assert ops.use_pallas_bwd()
    g_pallas = ops._fc_bwd(2, 32, 1e-6, True, None, None, res, do)
    monkeypatch.setenv("REPRO_FASTMAX_BWD", "jnp")
    g_jnp = ops._fc_bwd(2, 32, 1e-6, True, None, None, res, do)
    for a, b in zip(g_pallas, g_jnp):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        rel = np.abs(a - b).max() / max(np.abs(b).max(), 1e-6)
        assert rel <= tol, f"rel err {rel} > {tol}"


@pytest.mark.parametrize("p", [1, 2])
def test_forced_blocking_matches_unblocked(p):
    """Forcing small Dv carry blocks (nb in {2, 4, 8}) reproduces the
    unblocked (blk = Dv) forward outputs, emitted carry, and backward
    cotangents — the additive-over-Dv decomposition is exact, f64."""
    from repro.kernels.fastmax_causal import fastmax_causal_pallas
    from repro.kernels.fastmax_causal_bwd import fastmax_causal_bwd_pallas

    rng = np.random.default_rng(43 + p)
    b, hq, hkv, n, d, dv = 1, 4, 2, 33, 8, 16
    q, k, v = mk(rng, b, hq, hkv, n, d, dv, jnp.float64)
    do = jnp.asarray(rng.normal(size=(b, hq, n, dv)), jnp.float64)
    o_ref, st_ref = fastmax_causal_pallas(
        q, k, v, p=p, chunk_size=16, interpret=True, return_state=True,
        blk=dv)
    g_ref = fastmax_causal_bwd_pallas(
        q, k, v, tuple(st_ref), do, p=p, chunk_size=16, interpret=True,
        blk=dv)
    for blk in (8, 4, 2):
        o_b, st_b = fastmax_causal_pallas(
            q, k, v, p=p, chunk_size=16, interpret=True, return_state=True,
            blk=blk)
        np.testing.assert_allclose(np.asarray(o_b), np.asarray(o_ref),
                                   rtol=1e-12, atol=1e-12)
        for a, bb in zip(st_b, st_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-12, atol=1e-12)
        g_b = fastmax_causal_bwd_pallas(
            q, k, v, tuple(st_ref), do, p=p, chunk_size=16, interpret=True,
            blk=blk)
        for a, bb in zip(g_b, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-11, atol=1e-12)


def test_jnp_bwd_oracle_stays_wired(monkeypatch):
    """REPRO_FASTMAX_BWD=jnp reroutes the custom_vjp backward rule to the
    jnp §2.5 reverse scan (the interpret-mode oracle escape hatch); both
    rules produce the same cotangents from the same kernel-emitted
    residual."""
    from repro.kernels import ops
    rng = np.random.default_rng(21)
    q, k, v = mk(rng, 1, 2, 1, 32, 8, 8, jnp.float64)
    do = jnp.asarray(rng.normal(size=(1, 2, 32, 8)), jnp.float64)
    _, res = ops._fc_fwd(q, k, v, 2, 16, 1e-6, True, None, None)
    assert ops.use_pallas_bwd()
    g_pallas = ops._fc_bwd(2, 16, 1e-6, True, None, None, res, do)
    monkeypatch.setenv("REPRO_FASTMAX_BWD", "jnp")
    assert not ops.use_pallas_bwd()
    g_jnp = ops._fc_bwd(2, 16, 1e-6, True, None, None, res, do)
    for a, b in zip(g_pallas, g_jnp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("shape", [(1, 2, 1, 32, 8, 8),
                                   (2, 4, 2, 100, 16, 16)])
@pytest.mark.parametrize("p", [1, 2])
def test_forward_emits_final_state(shape, p):
    """return_state=True: the forward kernel's own carry == full-sequence
    moments (the prefill→decode handoff and the backward residual)."""
    from repro.core.fastmax import compute_moments
    rng = np.random.default_rng(hash((shape, p, "st")) % 2**31)
    q, k, v = mk(rng, *shape, jnp.float64)
    o, state = fastmax_prefill_kernel(q, k, v, p=p, chunk_size=16, interpret=True)
    ref_o = fastmax_ref(q, k, v, p=p, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref_o),
                               rtol=1e-9, atol=1e-9)
    mom = compute_moments(k, v, p=p)
    for got, want in zip(state, mom):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("p", [1, 2])
def test_decode_long_horizon_kernel_vs_jnp(p):
    """Prefill + 256 decode steps: the kernel-carried state stays in
    lockstep with the jnp moment step (no drift over a long horizon)."""
    from repro.core.fastmax import Moments
    from repro.core.decode_state import fastmax_decode_step
    rng = np.random.default_rng(31 + p)
    B, Hq, Hkv, N, D, Dv = 1, 2, 1, 16, 4, 4
    q, k, v = mk(rng, B, Hq, Hkv, N, D, Dv, jnp.float64)
    _, state_k = fastmax_prefill_kernel(q, k, v, p=p, chunk_size=8, interpret=True)
    state_j = Moments(*state_k)
    st_k = tuple(state_k)
    for i in range(256):
        q1, k1, v1 = mk(rng, B, Hq, Hkv, 1, D, Dv, jnp.float64)
        o_k, st_k = fastmax_decode(q1, k1, v1, st_k, p=p, interpret=True)
        o_j, state_j = fastmax_decode_step(state_j, q1, k1, v1, p=p,
                                           normalize=False)
        if i % 64 == 63 or i == 255:
            np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_j),
                                       rtol=1e-8, atol=1e-9)
    for a, b in zip(st_k, state_j):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-8, atol=1e-9)


def test_kernel_vs_oracle_decode_after_prefill_consistency():
    """Moment state built by full-sequence moments == kernel decode stream."""
    from repro.core.fastmax import compute_moments
    rng = np.random.default_rng(14)
    B, Hq, Hkv, N, D, Dv = 1, 2, 2, 24, 8, 8
    q, k, v = mk(rng, B, Hq, Hkv, N, D, Dv, jnp.float64)
    mom = compute_moments(k[:, :, :N - 1], v[:, :, :N - 1], p=2)
    o_k, _ = fastmax_decode(q[:, :, N - 1:], k[:, :, N - 1:], v[:, :, N - 1:],
                            tuple(mom), p=2, interpret=True)
    full = fastmax_ref(q, k, v, p=2, causal=True)
    np.testing.assert_allclose(np.asarray(o_k[:, :, 0]),
                               np.asarray(full[:, :, N - 1]),
                               rtol=1e-9, atol=1e-9)
