"""Per-phase attention benchmark: prefill / decode / backward per backend.

The perf-trajectory suite behind `BENCH_attention.json` (make bench-json):
one row per (backend, phase) so the prefill, single-token decode, and
training-backward costs of fastmax-kernel vs fastmax-chunked vs softmax are
tracked across PRs. All three phases go through the production surfaces
(`repro.attention` prefill/step protocol + `attention()` dispatcher), so a
routing regression shows up here too.

On CPU the Pallas backends run in interpret mode (REPRO_DECODE_KERNEL=1 is
set for the fastmax-kernel decode row so the kernel path is exercised, not
the jnp fallback) — absolute numbers are only comparable within a machine,
which is exactly what a committed per-repo baseline is for.
"""
from __future__ import annotations

import os
import sys

import numpy as np

from benchmarks.common import csv_row, time_fn

# hybrid2-kernel: near/far-field backend — prefill/backward go through the
# hybrid Pallas kernel (interpret off-TPU), decode through the two-leg jnp
# state step (moments + rolling window), tracked like every other cell
SPECS = ("softmax", "fastmax2", "fastmax2-kernel", "hybrid2-kernel")

# TP>1 decode cell: the shard_map-wrapped Pallas decode kernel vs the jnp
# feature-TP moment step it replaced as the tensor-parallel serving path.
# Runs in THIS process on the devices present (a child that needs the chip
# would fight the parent for it): a (data=n/4, model=4) mesh with kv heads
# NOT dividing 'model' (the GQA feature-TP regime of the production
# configs). Dropped where fewer than 4 devices exist.
def _bench_tp_decode(*, quick: bool):
    import time

    import jax
    import jax.numpy as jnp

    from repro.attention import AttentionSpec, init_state, prefill, step
    from repro.kernels import autotune
    from repro.launch.mesh import make_test_mesh

    n_dev = len(jax.devices())
    if n_dev < 4 or n_dev % 4:
        print(f"attn_phases: tp-decode cell needs a multiple of 4 devices; "
              f"this process has {n_dev} -> cell not run", file=sys.stderr)
        return None
    b, hq, hkv, n, d, dv, iters, steps = (
        (2, 4, 2, 128, 16, 16, 3, 8) if quick
        else (4, 8, 2, 1024, 64, 64, 5, 16))
    b *= n_dev // 4
    spec = AttentionSpec(family="fastmax", p=2, impl="kernel", chunk_size=64)
    rng = np.random.default_rng(0)

    def mkq(m):
        return (jnp.asarray(rng.normal(size=(b, hq, m, d)), jnp.float32),
                jnp.asarray(rng.normal(size=(b, hkv, m, d)), jnp.float32),
                jnp.asarray(rng.normal(size=(b, hkv, m, dv)), jnp.float32))

    q, k, v = mkq(n)
    q1, k1, v1 = mkq(1)
    mesh = make_test_mesh((n_dev // 4, 4), ("data", "model"))
    res = {}
    with mesh:
        for key, env in (("decode_us", "1"), ("decode_jnp_us", "0")):
            os.environ["REPRO_DECODE_KERNEL"] = env
            st = init_state(spec, batch=b, n_kv_heads=hkv, q_head_dim=d,
                            v_head_dim=dv, max_len=n + 1)
            _, st = prefill(q, k, v, spec, state=st)
            fn = jax.jit(lambda st, q, k, v: step(st, q, k, v, spec))
            o, _ = fn(st, q1, k1, v1)
            o.block_until_ready()
            ts = []
            for _ in range(iters):
                t0 = time.perf_counter()
                for _ in range(steps):
                    o, _ = fn(st, q1, k1, v1)
                o.block_until_ready()
                ts.append((time.perf_counter() - t0) / steps)
            res[key] = min(ts) * 1e6
    snap = autotune.snapshot_lookups()
    res["schedule"] = {r["key"]: r["schedule"] for r in snap}
    res["autotune_cache"] = {r["key"]: r["cache"] for r in snap}
    res["hardware"] = autotune.hardware_label()
    return res


def _mk(rng, b, hq, hkv, n, d, dv, dtype):
    import jax.numpy as jnp
    q = jnp.asarray(rng.normal(size=(b, hq, n, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, n, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, n, dv)), dtype)
    return q, k, v


def _bench_spec(name: str, *, b, hq, hkv, n, d, dv, n_steps, iters):
    import jax
    import jax.numpy as jnp
    from repro.attention import (AttentionSpec, attention, init_state,
                                 prefill, step)
    from repro.kernels import autotune

    autotune.clear_lookups()
    spec = AttentionSpec.parse(name)
    rng = np.random.default_rng(0)
    q, k, v = _mk(rng, b, hq, hkv, n, d, dv, jnp.float32)
    q1, k1, v1 = _mk(rng, b, hq, hkv, 1, d, dv, jnp.float32)

    st0 = init_state(spec, batch=b, n_kv_heads=hkv, q_head_dim=d,
                     v_head_dim=dv, max_len=n + n_steps)

    prefill_fn = jax.jit(lambda q, k, v, st: prefill(q, k, v, spec, state=st))
    _, st = prefill_fn(q, k, v, st0)
    t_prefill = time_fn(lambda: prefill_fn(q, k, v, st0)[0], iters=iters)

    step_fn = jax.jit(lambda st, q, k, v: step(st, q, k, v, spec))
    t_decode = time_fn(lambda: step_fn(st, q1, k1, v1)[0], iters=iters)

    grad_fn = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attention(q, k, v, spec, causal=True)),
        argnums=(0, 1, 2)))
    t_backward = time_fn(lambda: grad_fn(q, k, v), iters=iters)

    res = {
        "prefill_us": t_prefill * 1e6,
        "decode_us": t_decode * 1e6,
        "backward_us": t_backward * 1e6,
    }
    # schedule provenance (kernel cells only — the jnp/softmax suites make
    # no kernel launches and record nothing): the chosen schedule per
    # kernel launch, plus the autotune cache verdict, so perf regressions
    # are attributable to schedule changes and the >20% rule never
    # compares cross-schedule (benchmarks.common.regression_summary)
    snap = autotune.snapshot_lookups()
    if snap:
        res["schedule"] = {r["key"]: r["schedule"] for r in snap}
        res["autotune_cache"] = {r["key"]: r["cache"] for r in snap}
        res["hardware"] = autotune.hardware_label()
    return res


def collect(quick: bool = True) -> dict:
    """Structured results: {meta, suites: {backend: {phase_us: float}}}."""
    import jax

    shape = (dict(b=1, hq=4, hkv=2, n=256, d=16, dv=16, n_steps=4, iters=5)
             if quick else
             dict(b=2, hq=8, hkv=4, n=2048, d=64, dv=64, n_steps=8, iters=5))
    # exercise the native-state decode kernel (interpret off-TPU), not the
    # jnp fallback — this suite tracks the kernel path. The autotuner runs
    # in `offline` mode unless the caller chose one: the committed cache +
    # deterministic cost model pick every schedule (never timing Python
    # loops mid-bench), and each cell records the schedule it ran.
    prev = {var: os.environ.get(var)
            for var in ("REPRO_DECODE_KERNEL", "REPRO_AUTOTUNE")}
    os.environ["REPRO_DECODE_KERNEL"] = "1"
    os.environ.setdefault("REPRO_AUTOTUNE", "offline")
    try:
        suites = {name: _bench_spec(name, **shape) for name in SPECS}
        # TP>1 decode: shard_map kernel vs the jnp feature-TP step, in
        # process on the devices present (None where there are too few)
        tp = _bench_tp_decode(quick=quick)
        if tp is not None:
            suites["fastmax2-kernel-tp4"] = tp
    finally:
        for var, val in prev.items():
            if val is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = val
    # off-TPU the Pallas suites run interpret-mode kernel bodies: label the
    # cells so the regression check only ever compares like with like
    # (interpret timings are Python-loop-bound and NOT comparable to either
    # compiled-TPU numbers or the pure-jnp suites' XLA timings)
    if jax.default_backend() != "tpu":
        for name in suites:
            if "kernel" in name:
                suites[name]["interpret"] = True
    return {
        "meta": {
            "platform": jax.default_backend(),
            "quick": quick,
            "shape": shape,
        },
        "suites": suites,
    }


def rows(results: dict):
    """CSV rows for a `collect()` result — the one place the
    `attn_phases/<suite>/<phase>` naming lives."""
    for name, phases in results["suites"].items():
        for phase, us in phases.items():
            if not phase.endswith("_us"):
                continue   # cell annotations (e.g. `interpret`), not timings
            yield csv_row(f"attn_phases/{name}/{phase[:-3]}", us)


def run(quick: bool = True):
    yield from rows(collect(quick=quick))
