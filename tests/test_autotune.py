"""Schedule autotuner: forced-schedule parity, cache behavior, env modes.

The load-bearing property: a schedule changes WHERE work happens (block
shapes, chunking, grid semantics), never WHAT is computed — so every
candidate schedule the tuner can emit must produce the same outputs and
gradients as the untuned default, for all four kernels. f64 runs pin that
to ~1e-12 (summation-order-level); f32 gets a looser tol. On top of that:
cache round-trip + determinism, and the REPRO_AUTOTUNE=0 escape hatch
being byte-identical to calling the kernels with no autotuner at all.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import init_fastmax_state
from repro.core.ref import normalize_qk
from repro.kernels import autotune, ops
from repro.kernels.autotune import (CACHE_VERSION, Schedule, ShapeKey,
                                    build_gate_entries, candidate_schedules,
                                    cost_model, default_schedule, key_str,
                                    load_cache, lookup_schedule, save_cache,
                                    tune)
from repro.kernels.fastmax_causal import fastmax_causal_pallas
from repro.kernels.fastmax_causal_bwd import fastmax_causal_bwd_pallas
from repro.kernels.fastmax_decode import fastmax_decode_pallas
from repro.kernels.fastmax_noncausal import fastmax_noncausal_pallas
from repro.kernels.tiling import divisors, pick_blk, pick_bm

jax.config.update("jax_enable_x64", True)

pytestmark = pytest.mark.kernels


def mk(rng, b, hq, hkv, n, d, dv, dtype):
    q = normalize_qk(jnp.asarray(rng.normal(size=(b, hq, n, d)), dtype))
    k = normalize_qk(jnp.asarray(rng.normal(size=(b, hkv, n, d)), dtype))
    v = jnp.asarray(rng.normal(size=(b, hkv, n, dv)), dtype)
    return q, k, v


# parity shape: small enough that interpret-mode sweeps stay fast, with a
# non-divisor N (padding in play) and a nontrivial candidate space
B, HQ, HKV, N, D, DV = 1, 4, 2, 40, 4, 4
DTYPES = [(jnp.float64, 1e-12), (jnp.float32, 2e-5)]


def _key(kernel, dtype, n=N):
    return ShapeKey(kernel, n, D, DV, HQ // HKV, 2,
                    jnp.dtype(dtype).name, "cpu")


@pytest.fixture(autouse=True)
def _clean_lookup_state(monkeypatch, tmp_path):
    """Each test gets autotune OFF by default and a throwaway cache path
    (never the committed in-repo cache), with the provenance log reset."""
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache.json"))
    autotune.clear_lookups()
    yield
    autotune.clear_lookups()


# ---------------------------------------------------------------------------
# tiling pickers (satellite: divisor enumeration + budget validation)
# ---------------------------------------------------------------------------

def test_divisors_enumeration():
    assert divisors(1) == (1,)
    assert divisors(12) == (1, 2, 3, 4, 6, 12)
    assert divisors(128) == (1, 2, 4, 8, 16, 32, 64, 128)
    for bad in (0, -3, 2.5, "8"):
        with pytest.raises(ValueError):
            divisors(bad)


@pytest.mark.parametrize("d", [1, 4, 16, 64, 128, 96])
def test_pick_bm_matches_linear_scan(d):
    for budget in (1, 8, 512, 2048, 10**6):
        brute = max(bm for bm in range(1, d + 1)
                    if d % bm == 0 and bm * d <= budget) if any(
                        d % bm == 0 and bm * d <= budget
                        for bm in range(1, d + 1)) else 1
        assert pick_bm(d, budget) == max(brute, 1)


@pytest.mark.parametrize("d,dv", [(4, 4), (16, 16), (64, 64), (128, 128),
                                  (128, 8), (128, 256), (64, 512)])
def test_pick_blk_matches_linear_scan(d, dv):
    tileable = [blk for blk in range(1, dv + 1)
                if dv % blk == 0 and (blk == dv or blk % 128 == 0)]
    for budget in (1, d * d, 1 << 20, 2 << 20):
        feas = [blk for blk in tileable if d * d * blk <= budget]
        assert pick_blk(d, dv, budget) == (max(feas) if feas
                                           else min(tileable))


def test_pickers_validate_budget():
    for bad in (0, -1, 1.5, "512"):
        with pytest.raises(ValueError):
            pick_bm(8, bad)
        with pytest.raises(ValueError):
            pick_blk(8, 8, bad)
    with pytest.raises(ValueError):
        pick_blk(0, 8)


# ---------------------------------------------------------------------------
# candidate space sanity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", autotune.KERNELS)
def test_candidates_are_valid_and_contain_default(kernel):
    key = _key(kernel, jnp.float32, n=1 if kernel == "decode" else N)
    cands = candidate_schedules(kernel, key, 128)
    assert default_schedule(kernel, D, DV, 128) in cands
    assert len(cands) == len(set(cands))
    for s in cands:
        assert D % s.bm == 0
        assert DV % s.blk == 0
        assert s.chunk_size >= 1
        assert s.grid in ("parallel", "arbitrary")


def test_candidates_rejects_unknown_kernel():
    with pytest.raises(ValueError):
        candidate_schedules("flash", _key("causal_fwd", jnp.float32), 128)


def test_cost_model_flags_vmem_infeasible():
    # a 256x256 p=2 head with an unblocked bwd carry pair (2 * D^2 * Dv * 4
    # = 128 MiB of scratch alone) cannot fit the kernels' VMEM limit; a
    # 128-wide Dv block of a 128x128 head is lane-tileable and fits, a
    # 64-wide one does not tile on the TPU at all
    big = ShapeKey("causal_bwd", 1024, 256, 256, 4, 2, "float32", "cpu")
    assert math.isinf(cost_model(big, Schedule(bm=1, blk=256, chunk_size=128,
                                               grid="parallel")))
    key = ShapeKey("causal_bwd", 1024, 128, 128, 4, 2, "float32", "cpu")
    misaligned = Schedule(bm=1, blk=64, chunk_size=128, grid="parallel")
    good = Schedule(bm=1, blk=pick_blk(128, 128, 1 << 20), chunk_size=128,
                    grid="parallel")
    assert math.isinf(cost_model(key, misaligned))
    assert math.isfinite(cost_model(key, good))


@pytest.mark.parametrize("kernel", autotune.KERNELS)
def test_schedules_tile_on_tpu_at_128(kernel):
    """At D = Dv = 128 the pickers and every autotune candidate give blocks
    the TPU compiler can tile: a Dv block of 128 lanes (nb = 1) and m2 row
    blocks of a multiple of 8 rows."""
    key = ShapeKey(kernel, 1 if kernel == "decode" else 4096, 128, 128, 2,
                   2, "bfloat16", "tpu")
    budget = (autotune.BWD_BLK_BUDGET if kernel == "causal_bwd"
              else autotune.FWD_BLK_BUDGET)
    assert pick_blk(128, 128, budget) == 128
    assert (pick_bm(128) * 128) % 8 == 0
    cands = candidate_schedules(kernel, key, 128)
    assert cands
    for s in cands:
        assert s.blk == 128 and (s.bm * 128) % 8 == 0, s
        assert autotune.tpu_tileable(s, 128, 128)
        assert math.isfinite(cost_model(key, s)), s


# ---------------------------------------------------------------------------
# forced-schedule parity: every candidate == default, all four kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", DTYPES)
def test_causal_fwd_schedule_parity(dtype, tol):
    rng = np.random.default_rng(0)
    q, k, v = mk(rng, B, HQ, HKV, N, D, DV, dtype)
    o0, st0 = fastmax_causal_pallas(q, k, v, p=2, interpret=True,
                                    return_state=True)
    for s in candidate_schedules("causal_fwd", _key("causal_fwd", dtype),
                                 128):
        o, st = fastmax_causal_pallas(
            q, k, v, p=2, interpret=True, return_state=True,
            chunk_size=s.chunk_size, bm=s.bm, blk=s.blk, grid=s.grid)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o0),
                                   rtol=tol, atol=tol, err_msg=str(s))
        for a, b in zip(st, st0):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=tol, atol=tol, err_msg=str(s))


@pytest.mark.parametrize("dtype,tol", DTYPES)
def test_causal_bwd_schedule_parity(dtype, tol):
    rng = np.random.default_rng(1)
    q, k, v = mk(rng, B, HQ, HKV, N, D, DV, dtype)
    do = jnp.asarray(rng.normal(size=(B, HQ, N, DV)), dtype)
    _, st = fastmax_causal_pallas(q, k, v, p=2, interpret=True,
                                  return_state=True)
    g0 = fastmax_causal_bwd_pallas(q, k, v, st, do, p=2, interpret=True)
    for s in candidate_schedules("causal_bwd", _key("causal_bwd", dtype),
                                 128):
        g = fastmax_causal_bwd_pallas(
            q, k, v, st, do, p=2, interpret=True,
            chunk_size=s.chunk_size, bm=s.bm, blk=s.blk, grid=s.grid)
        for a, b, name in zip(g, g0, ("dq", "dk", "dv")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=tol, atol=tol,
                                       err_msg=f"{name} {s}")


@pytest.mark.parametrize("dtype,tol", DTYPES)
def test_decode_schedule_parity(dtype, tol):
    rng = np.random.default_rng(2)
    q, k, v = mk(rng, B, HQ, HKV, 1, D, DV, dtype)
    st = tuple(init_fastmax_state(B, HKV, D, DV, p=2, dtype=dtype))
    o0, ns0 = fastmax_decode_pallas(q, k, v, st, p=2, interpret=True)
    for s in candidate_schedules("decode", _key("decode", dtype, n=1), 128):
        o, ns = fastmax_decode_pallas(q, k, v, st, p=2, interpret=True,
                                      bm=s.bm, grid=s.grid)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o0),
                                   rtol=tol, atol=tol, err_msg=str(s))
        for a, b in zip(ns, ns0):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=tol, atol=tol, err_msg=str(s))


@pytest.mark.parametrize("dtype,tol", DTYPES)
def test_noncausal_schedule_parity(dtype, tol):
    rng = np.random.default_rng(3)
    q, k, v = mk(rng, B, HQ, HKV, N, D, DV, dtype)
    o0 = fastmax_noncausal_pallas(q, k, v, p=2, interpret=True)
    for s in candidate_schedules("noncausal", _key("noncausal", dtype), 128):
        o = fastmax_noncausal_pallas(q, k, v, p=2, interpret=True,
                                     chunk_size=s.chunk_size, bm=s.bm,
                                     grid=s.grid)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o0),
                                   rtol=tol, atol=tol, err_msg=str(s))


def test_chunk_size_variation_parity():
    """Chunking differs across these (N=100 splits as 4x32 / 1x100-pad),
    so this is the one place cross-chunk summation order actually moves."""
    rng = np.random.default_rng(4)
    q, k, v = mk(rng, 1, 4, 2, 100, 8, 8, jnp.float64)
    o0 = fastmax_causal_pallas(q, k, v, p=2, interpret=True, chunk_size=128)
    for cs in (16, 32, 64):
        o = fastmax_causal_pallas(q, k, v, p=2, interpret=True,
                                  chunk_size=cs)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o0),
                                   rtol=1e-12, atol=1e-12)


def test_grads_through_forced_schedule():
    """ops.fastmax(schedule=...) differentiates: the custom_vjp threads the
    forced schedule through both the fwd and bwd kernels."""
    rng = np.random.default_rng(5)
    q, k, v = mk(rng, B, HQ, HKV, N, D, DV, jnp.float64)

    def loss(q, k, v, schedule=None):
        return jnp.sum(ops.fastmax(q, k, v, p=2, causal=True,
                                   interpret=True, schedule=schedule) ** 2)

    g0 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    forced = Schedule(bm=2, blk=2, chunk_size=16, grid="arbitrary")
    g1 = jax.grad(lambda *a: loss(*a, schedule=forced))(q, k, v)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-12, atol=1e-12)


# ---------------------------------------------------------------------------
# env modes + byte-identity of the escape hatch
# ---------------------------------------------------------------------------

def test_mode_off_is_byte_identical(monkeypatch):
    rng = np.random.default_rng(6)
    q, k, v = mk(rng, B, HQ, HKV, N, D, DV, jnp.float32)
    base = fastmax_causal_pallas(q, k, v, p=2, interpret=True)

    for env in (None, "0"):
        if env is None:
            monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
        else:
            monkeypatch.setenv("REPRO_AUTOTUNE", env)
        out = ops.fastmax(q, k, v, p=2, causal=True, interpret=True)
        assert np.asarray(out).tobytes() == np.asarray(base).tobytes()

    # off-mode lookups return None but still record provenance
    assert lookup_schedule("causal_fwd", n=N, d=D, dv=DV, g=2, p=2,
                           dtype=jnp.float32, chunk_size=128) is None
    recs = autotune.snapshot_lookups()
    assert recs and recs[-1]["cache"] == "off"
    assert recs[-1]["source"] == "default"


def test_mode_validation():
    import os
    os.environ["REPRO_AUTOTUNE"] = "banana"
    try:
        with pytest.raises(ValueError):
            autotune.autotune_mode()
    finally:
        del os.environ["REPRO_AUTOTUNE"]


def test_offline_mode_uses_cache_then_cost_model(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE", "offline")
    path = tmp_path / "cache.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))

    # miss -> deterministic cost-model winner, nothing written (offline
    # never persists)
    s1 = lookup_schedule("causal_fwd", n=N, d=D, dv=DV, g=2, p=2,
                         dtype=jnp.float32, chunk_size=128)
    assert isinstance(s1, Schedule)
    assert autotune.snapshot_lookups()[-1]["cache"] == "miss"
    assert not path.exists()

    # a planted cache entry wins over the cost model
    planted = Schedule(bm=2, blk=DV, chunk_size=64, grid="arbitrary")
    key = _key("causal_fwd", jnp.float32)
    save_cache(str(path), {key_str(key): {
        "schedule": dict(planted._asdict()), "source": "measured"}})
    autotune.clear_lookups()
    s2 = lookup_schedule("causal_fwd", n=N, d=D, dv=DV, g=2, p=2,
                         dtype=jnp.float32, chunk_size=128)
    assert s2 == planted
    rec = autotune.snapshot_lookups()[-1]
    assert rec["cache"] == "hit" and rec["source"] == "measured"


def test_stale_cache_entry_treated_as_miss(monkeypatch, tmp_path):
    """An entry whose blocks no longer divide the dims (code/schema drift)
    must not crash the kernels — it falls back to a fresh tune."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "offline")
    path = tmp_path / "cache.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    key = _key("causal_fwd", jnp.float32)
    save_cache(str(path), {key_str(key): {
        "schedule": {"bm": 3, "blk": 3, "chunk_size": 128,
                     "grid": "parallel"}, "source": "measured"}})
    s = lookup_schedule("causal_fwd", n=N, d=D, dv=DV, g=2, p=2,
                        dtype=jnp.float32, chunk_size=128)
    assert isinstance(s, Schedule) and D % s.bm == 0 and DV % s.blk == 0
    assert autotune.snapshot_lookups()[-1]["cache"] == "miss"


def test_on_mode_persists_only_to_explicit_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    path = tmp_path / "mine.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    s = lookup_schedule("decode", n=1, d=D, dv=DV, g=2, p=2,
                        dtype=jnp.float32, chunk_size=128)
    assert isinstance(s, Schedule)
    entries = load_cache(str(path))
    key = key_str(_key("decode", jnp.float32, n=1))
    assert entries[key]["schedule"] == dict(s._asdict())
    # and a rerun is a hit
    autotune.clear_lookups()
    assert lookup_schedule("decode", n=1, d=D, dv=DV, g=2, p=2,
                           dtype=jnp.float32, chunk_size=128) == s
    assert autotune.snapshot_lookups()[-1]["cache"] == "hit"


# ---------------------------------------------------------------------------
# cache round-trip + determinism + committed-cache freshness
# ---------------------------------------------------------------------------

def test_cache_round_trip(tmp_path):
    path = tmp_path / "rt.json"
    entries = {"k1": {"schedule": {"bm": 2, "blk": 4, "chunk_size": 128,
                                   "grid": "parallel"},
                      "source": "cost_model", "score": 1e-6}}
    save_cache(str(path), entries)
    assert load_cache(str(path)) == entries
    raw = json.loads(path.read_text())
    assert raw["version"] == CACHE_VERSION

    # version drift -> ignored wholesale
    raw["version"] = CACHE_VERSION + 1
    path.write_text(json.dumps(raw))
    assert load_cache(str(path)) == {}


def test_tune_is_deterministic():
    key = _key("causal_fwd", jnp.float32)
    r1 = tune(key, 128, allow_measure=False)
    r2 = tune(key, 128, allow_measure=False)
    assert r1 == r2
    assert r1[1] == "cost_model"


def test_gate_entries_deterministic_and_match_committed():
    e1 = build_gate_entries()
    e2 = build_gate_entries()
    assert e1 == e2
    # the committed cache (shipped for the dryrun-gate + bench shapes) must
    # agree with a fresh sweep — the same check CI's autotune job runs
    committed = load_cache(autotune.DEFAULT_CACHE)
    assert committed, "committed autotune_cache.json missing or unreadable"
    for ks, entry in e1.items():
        assert committed[ks]["schedule"] == entry["schedule"], ks
