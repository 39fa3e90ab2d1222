"""Shared benchmark helpers."""
from __future__ import annotations

import time

import jax
import numpy as np

__all__ = ["time_fn", "csv_row", "regression_summary",
           "REGRESSION_THRESHOLD"]

REGRESSION_THRESHOLD = 1.20


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-clock seconds per call (jitted fns get compiled in
    warmup)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def csv_row(name: str, us_per_call: float, derived: str = "") -> str:
    return f"{name},{us_per_call:.1f},{derived}"


# cell annotation keys that, when they differ between baseline and fresh,
# make the cell's timings incomparable — the regression check skips the
# suite instead of warning on it:
#   interpret   forced-host-device / off-TPU Pallas cells (Python-loop
#               timings, never comparable to compiled ones)
#   hardware    bench-tpu lane label ("tpu" vs "<platform>-interpret")
#   schedule    the autotuned kernel schedule — a changed schedule changes
#               the measured thing, so the >20% rule can't attribute the
#               delta to a code regression
_LABEL_KEYS = ("interpret", "hardware", "schedule")


def regression_summary(baseline: dict, fresh: dict) -> str:
    """One fail-soft line comparing fresh phase timings to the baseline.

    Used by `benchmarks/run.py` (BENCH_attention.json). Only `*_us` keys are
    timings; other cell keys are annotations. A suite whose `interpret`,
    `hardware`, or `schedule` label differs from the baseline's is skipped
    entirely: those cells time a different thing (interpret vs compiled,
    other silicon, other kernel schedule), whatever `meta.platform` says.
    """
    if baseline.get("meta", {}).get("platform") != \
            fresh.get("meta", {}).get("platform") or \
            baseline.get("meta", {}).get("quick") != \
            fresh.get("meta", {}).get("quick"):
        return (f"bench-json: baseline platform/mode differs — regression "
                f"check skipped")
    slow, skipped = [], []
    for suite, phases in fresh.get("suites", {}).items():
        base_p = baseline.get("suites", {}).get(suite, {})
        if any(base_p.get(key) != phases.get(key) for key in _LABEL_KEYS):
            skipped.append(suite)
            continue
        for phase, us in phases.items():
            if not phase.endswith("_us"):
                continue
            b = base_p.get(phase)
            if b and us > b * REGRESSION_THRESHOLD:
                slow.append(f"{suite}/{phase[:-3]} {b:.0f}->{us:.0f}us")
    note = (f" (skipped label mismatch: {', '.join(skipped)})"
            if skipped else "")
    if slow:
        return (f"bench-json: WARNING — >20% slower than baseline: "
                + "; ".join(slow) + note)
    return f"bench-json: OK (no >20% regressions vs baseline){note}"
