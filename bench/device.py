"""The device guard and what the harness reads from JAX about the device.

A run measures a TPU listed in `peaks.json` or nothing: on any other
backend, too few chips, or an unknown `device_kind` it exits non-zero
before printing a result, so no CPU number ever appears under a device
metric's name.
"""
from __future__ import annotations

import json
import logging
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
# environment switches that would move the kernels off the default route
REROUTE_VARS = ("REPRO_DECODE_KERNEL", "REPRO_FASTMAX_BWD", "REPRO_AUTOTUNE")


class BenchError(RuntimeError):
    """The run cannot produce a valid result."""


def peaks(kind: str) -> dict:
    """Per-chip peaks of `kind`; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json "
                         f"({sorted(table)})")
    return table[kind]


def drop_reroutes() -> list:
    """Remove the reroute switches from the environment; returns those
    that were set."""
    return [v for v in REROUTE_VARS if os.environ.pop(v, None) is not None]


def device_label(chips: int) -> dict:
    """The devices this run measures, as JAX reports them. Raises unless
    JAX's backend is a TPU in the peaks table with at least `chips` chips."""
    import jax

    devs = jax.devices()
    label = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if label["platform"] != "tpu":
        raise BenchError(f"needs a TPU; JAX sees {label}")
    if label["count"] < chips:
        raise BenchError(f"needs {chips} chips; JAX sees {label}")
    peaks(label["kind"])
    label["count"] = chips
    return label


def peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first `chips` devices."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


class CompileClock:
    """Seconds JAX spent compiling, compiles, and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class RouteLog(logging.Handler):
    """Collects the program's attention routing lines."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.lines = []
        log = logging.getLogger("repro.attention")
        log.addHandler(self)
        log.setLevel(logging.INFO)

    def emit(self, record):
        self.lines.append(record.getMessage())

    def check(self, expect=()):
        bad = [m for m in self.lines
               if "interpret" in m or "fallback" in m or "disabled" in m]
        if bad:
            raise BenchError(f"kernels not on the compiled route: {bad}")
        for want in expect:
            if not any(want in m for m in self.lines):
                raise BenchError(f"routing line {want!r} missing from "
                                 f"{self.lines}")


def require_kernels(text: str, names) -> None:
    """The lowered program text calls the compiled Pallas kernels."""
    missing = [n for n in names if n not in text]
    if "tpu_custom_call" not in text or missing:
        raise BenchError(f"compiled kernels {missing or list(names)} not in "
                         f"the lowered program")


class Laps:
    """Wall and CPU seconds of this process (all its threads), sampled at
    the start of each step of the window and at the marks a driver sets
    inside it. `slowest()` describes the slowest step part by part, so that
    a stall reads as work on the host or as the host waiting."""

    def __init__(self, names):
        self.names = names          # one per part of a step
        self.steps = []

    def begin(self):
        self._cur = [(time.perf_counter(), time.process_time())]

    def mark(self):
        self._cur.append((time.perf_counter(), time.process_time()))

    def end(self):
        self.mark()
        self.steps.append(self._cur)

    def slowest(self, since=-float("inf")) -> str:
        steps = [s for s in self.steps if s[0][0] >= since]
        if not steps:
            return "no steps"
        walls = [s[-1][0] - s[0][0] for s in steps]
        i = max(range(len(walls)), key=walls.__getitem__)
        parts = ", ".join(
            f"{n} {1e3 * (b[0] - a[0]):.1f} ms wall {1e3 * (b[1] - a[1]):.1f}"
            f" ms cpu" for n, a, b in zip(self.names, steps[i], steps[i][1:]))
        return (f"slowest step {i} of {len(walls)}: {1e3 * walls[i]:.1f} ms "
                f"({parts})")


def say(*args) -> None:
    print(*args, file=sys.stderr, flush=True)
