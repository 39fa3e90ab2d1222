"""Schedule autotuner for the fastmax/hybrid Pallas kernels.

Every schedule knob in the kernel stack used to be a static guess:
`tiling.pick_bm`/`pick_blk` are fixed VMEM-budget heuristics and
`chunk_size=128` was hard-coded at every call site. This module sweeps a
candidate set of schedules per (kernel, shape, dtype, platform) and
persists the winners, XLA-autotune-cache style:

  Schedule   the four knobs threaded through `repro.kernels.ops` into the
             kernels: `bm` (m-major row block), `blk` (Dv carry column
             block, causal fwd/bwd only), `chunk_size` (sequence chunk C),
             and `grid` (dimension semantics of the independent grid axes:
             "parallel" lets Mosaic split them across megacore,
             "arbitrary" forces a single-core sequential sweep).
  ShapeKey   (kernel, N, D, Dv, G, p, dtype, platform) — B and Hkv scale
             every candidate identically (they only widen the
             embarrassingly-parallel head axis), so they stay out of the
             key and one entry serves all batch sizes.

Two scoring backends:

  * measured — compile the kernel with the forced schedule and time it on
    the real device (median-of-k, warmup, block_until_ready). Only on TPU,
    and only outside an active trace (a lookup from inside someone's jit
    falls back to the cost model rather than running kernels mid-trace).
  * cost model — a deterministic analytic estimate (MXU-matmul flops, HBM
    bytes, per-grid-program overhead, VMEM-residency feasibility). This is
    the ONLY backend in interpret mode: CPU containers must never rank
    schedules by timing Python loops.

Env protocol (read per lookup, so tests can flip it):

  REPRO_AUTOTUNE=0 | unset   off — `lookup_schedule` returns None and the
                             kernels run their untuned `pick_*` defaults,
                             byte-identical to an autotune-free build.
  REPRO_AUTOTUNE=1           on — cache lookup; on a miss, tune (measure
                             on TPU, cost model elsewhere). The winner is
                             persisted back to REPRO_AUTOTUNE_CACHE when
                             that env var is explicitly set (the runtime
                             never mutates the committed in-repo cache).
  REPRO_AUTOTUNE=offline     cache lookup; on a miss, cost model only —
                             deterministic everywhere, never measures.
  REPRO_AUTOTUNE_CACHE=path  cache file (default: the committed
                             `src/repro/kernels/autotune_cache.json`).

Every lookup (including mode=off) records a provenance entry —
schedule + cache hit/miss/off + source — in a module-level log that the
benchmarks (`BENCH_attention.json` cells) and the dry-run (`attn_schedule`
next to `attn_routing`) snapshot, so perf regressions are attributable to
schedule changes.

CLI (the committed-cache workflow, `make autotune` / CI autotune job):

  python -m repro.kernels.autotune --write   # retune gate shapes, write
  python -m repro.kernels.autotune --check   # fail if committed is stale

The gate shapes are the dryrun-gate kernel cells (qwen2.5-32b train_4k /
decode_32k at TP=16 feature mode) plus the bench-json quick/full shapes.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import NamedTuple, Optional

from repro.kernels.tiling import (BWD_BLK_BUDGET, FWD_BLK_BUDGET,
                                  KERNEL_BM_BUDGET, VMEM_LIMIT_BYTES,
                                  divisors, lane_tileable, pick_blk, pick_bm)

__all__ = ["Schedule", "ShapeKey", "KERNELS", "autotune_mode",
           "default_schedule", "candidate_schedules", "cost_model",
           "measure", "tune", "lookup_schedule", "load_cache", "save_cache",
           "tpu_tileable",
           "key_str", "hardware_label", "clear_lookups", "snapshot_lookups",
           "gate_keys", "build_gate_entries", "DEFAULT_CACHE",
           "CACHE_VERSION"]

KERNELS = ("causal_fwd", "causal_bwd", "decode", "noncausal", "hybrid_fwd")
GRIDS = ("parallel", "arbitrary")

CACHE_VERSION = 1
DEFAULT_CACHE = os.path.join(os.path.dirname(__file__),
                             "autotune_cache.json")

# cost-model chip constants (v5e-class). Absolute seconds are irrelevant —
# only the deterministic RANKING of candidates matters.
MXU_FLOPS = 197e12          # peak matmul flop/s
HBM_BW = 819e9              # bytes/s
VMEM_BYTES = VMEM_LIMIT_BYTES  # the kernels' scoped-VMEM limit
GRID_STEP_S = 2e-6          # fixed per-grid-program overhead
MEGACORE = 2                # "parallel" grid dims split across cores


class Schedule(NamedTuple):
    """One concrete kernel schedule (all knobs static / hashable)."""

    bm: int          # m-major row block (divides D)
    blk: int         # Dv carry column block (divides Dv; == Dv when unused)
    chunk_size: int  # sequence chunk C
    grid: str        # "parallel" | "arbitrary" (independent grid axes)


class ShapeKey(NamedTuple):
    kernel: str
    n: int
    d: int
    dv: int
    g: int
    p: int
    dtype: str
    platform: str


def key_str(key: ShapeKey) -> str:
    return (f"{key.kernel}|n={key.n},d={key.d},dv={key.dv},g={key.g},"
            f"p={key.p}|{key.dtype}|{key.platform}")


def autotune_mode() -> str:
    """'off' | 'on' | 'offline' from REPRO_AUTOTUNE (default off)."""
    env = os.environ.get("REPRO_AUTOTUNE", "0").strip().lower()
    if env in ("", "0", "off", "never"):
        return "off"
    if env in ("1", "on", "always"):
        return "on"
    if env == "offline":
        return "offline"
    raise ValueError(f"REPRO_AUTOTUNE={env!r}; expected 0, 1, or offline")


def _platform() -> str:
    import jax
    return jax.default_backend()


def hardware_label() -> str:
    """Bench-cell hardware label: compiled TPU vs interpret-mode host.

    The kernels compile only on TPU; everywhere else the Pallas bodies run
    in interpret mode, so off-TPU kernel timings are labeled
    '<platform>-interpret' and are never comparable across that boundary.
    """
    plat = _platform()
    return plat if plat == "tpu" else f"{plat}-interpret"


# ---------------------------------------------------------------------------
# candidate space
# ---------------------------------------------------------------------------

def default_schedule(kernel: str, d: int, dv: int,
                     chunk_size: int) -> Schedule:
    """The untuned schedule — exactly what the kernels pick on their own."""
    if kernel in ("causal_fwd", "hybrid_fwd"):
        blk = pick_blk(d, dv, FWD_BLK_BUDGET)
    elif kernel == "causal_bwd":
        blk = pick_blk(d, dv, BWD_BLK_BUDGET)
    else:
        blk = dv   # decode / noncausal carry the full Dv width
    return Schedule(bm=pick_bm(d), blk=blk, chunk_size=chunk_size,
                    grid="parallel")


def candidate_schedules(kernel: str, key: ShapeKey,
                        chunk_size: int = 128) -> list:
    """The bounded sweep set for one kernel/shape (always contains the
    untuned default). Every emitted schedule is valid: bm | D, blk | Dv,
    both tile on the TPU (`tpu_tileable`), and the scratch tuples fit the
    VMEM feasibility cap — the parity tests sweep exactly this list
    against the default schedule."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; expected {KERNELS}")
    d, dv, n = key.d, key.dv, key.n

    # bm: largest 3 divisors of D whose [bm*D, blk] tile stays MXU-sized
    bms = [bm for bm in divisors(d) if bm * d <= 4 * KERNEL_BM_BUDGET
           and _rows_tileable(bm, d)][-3:]

    if kernel in ("causal_fwd", "causal_bwd", "hybrid_fwd"):
        ntuples = 2 if kernel == "causal_bwd" else 1
        cap = VMEM_BYTES // 2    # leave headroom for the I/O tiles
        tileable = [b for b in divisors(dv) if lane_tileable(b, dv)]
        blks = [b for b in tileable
                if ntuples * d * d * b * 4 <= cap][-3:] or tileable[:1]
    else:
        blks = [dv]

    if kernel == "decode":
        chunks = [chunk_size]    # single-token step: no sequence chunking
    else:
        eff = {}
        for c in sorted({64, 128, 256, chunk_size}):
            eff.setdefault(min(c, max(8, n)), c)   # dedupe by effective C
        chunks = sorted(eff.values())[:3]

    out, seen = [], set()
    for sched in ([default_schedule(kernel, d, dv, chunk_size)]
                  + [Schedule(bm, blk, c, grid)
                     for bm in bms for blk in blks for c in chunks
                     for grid in GRIDS]):
        if sched not in seen:
            seen.add(sched)
            out.append(sched)
    return out


def _rows_tileable(bm: int, d: int) -> bool:
    """The [bm·D, ·] m2 row blocks obey the TPU's 8-row sublane tile."""
    return (bm * d) % 8 == 0 or bm == d


def tpu_tileable(sched: Schedule, d: int, dv: int) -> bool:
    """Whether the TPU compiler can tile `sched`'s blocks at (D, Dv)."""
    return _rows_tileable(sched.bm, d) and lane_tileable(sched.blk, dv)


# ---------------------------------------------------------------------------
# deterministic analytic cost model
# ---------------------------------------------------------------------------

def _roof(flops: float, bytes_: float) -> float:
    return max(flops / MXU_FLOPS, bytes_ / HBM_BW)


def cost_model(key: ShapeKey, sched: Schedule) -> float:
    """Estimated seconds per (batch x kv-head) launch; inf = infeasible.

    Models the real tradeoffs of each kernel: the Dv-blocking replicates
    the Dv-independent work (QK^T, denominator, g-carry) nb times but is
    what keeps the [D², blk] scratch inside VMEM; small bm/chunk pay fixed
    per-grid-program overhead; "parallel" grids split across megacore.
    """
    n, d, dv, g, p = key.n, key.d, key.dv, key.g, key.p
    bm, blk, c, grid = sched
    if not tpu_tileable(sched, d, dv):
        return math.inf
    inb = 2 if "bfloat16" in key.dtype or "float16" in key.dtype else 4
    d2 = d * d if p >= 2 else 1
    mega = MEGACORE if grid == "parallel" else 1

    if key.kernel in ("causal_fwd", "causal_bwd", "hybrid_fwd"):
        cs = min(c, max(8, n))
        nc = -(-n // cs)
        nb = dv // blk
        ntuples = 2 if key.kernel == "causal_bwd" else 1
        scratch = ntuples * (d2 * blk + d * blk + blk + d * d + d + 1) * 4
        io_tile = (g * cs * d + cs * d + cs * blk + g * cs * blk + cs) * inb
        if scratch + 2 * io_tile > VMEM_BYTES:
            return math.inf
        # per grid program (one chunk, one Dv block)
        flops = (2.0 * g * cs * cs * d            # QK^T   (Dv-independent)
                 + 2.0 * g * cs * cs * blk        # f(S) @ V
                 + 2.0 * g * cs * d * blk         # m1 contraction
                 + 2.0 * cs * d * blk)            # m1 update
        if p >= 2:
            flops += (2.0 * g * cs * d2 * blk     # m2 contraction
                      + 2.0 * cs * d2 * blk       # m2 update
                      + 2.0 * g * cs * d * d      # g2 denominator
                      + 2.0 * cs * d * d)         # g2 update
        if key.kernel == "hybrid_fwd":
            # band corrections: the previous-chunk score matmul and the
            # banded correction @ v (masking is elementwise; the block
            # shapes — and so the flops — don't depend on the window)
            flops += (2.0 * g * cs * cs * d       # prev-chunk QK^T
                      + 2.0 * g * cs * cs * blk)  # band corr @ V
            bytes_extra = (cs * d + cs * blk + cs) * inb  # prev k/v/mask
        else:
            bytes_extra = 0.0
        if key.kernel == "causal_bwd":
            # reversible reconstruct + recompute + 3 gradient matmuls +
            # carry-cotangent fold: ~2.5x the forward's per-chunk work
            flops *= 2.5
        bytes_ = io_tile + bytes_extra
        programs = nb * nc
        return (programs * _roof(flops, bytes_)
                + programs * GRID_STEP_S) / mega

    if key.kernel == "decode":
        nmb = d // bm if p >= 2 else 1
        tile = (bm * d * dv if p >= 2 else dv) * 4
        if 4 * tile > VMEM_BYTES:      # m2 block in + out, double-buffered
            return math.inf
        bytes_ = 2.0 * (d2 * dv + d * dv + dv + d * d + d + 1) * 4
        flops = 2.0 * (g + 1.0) * (d2 * dv + d * dv)
        return (_roof(flops, bytes_) + nmb * GRID_STEP_S) / mega

    # noncausal: phase A (moments) re-streams k/v once per m-block; phase B
    # (combine) re-reads the m2 tile once per query block
    cs = min(c, max(8, n))
    nc = -(-n // cs)
    nmb = d // bm if p >= 2 else 1
    tile = (bm * d * dv if p >= 2 else dv) * 4
    if 3 * tile + 2 * (cs * d + cs * dv) * inb > VMEM_BYTES:
        return math.inf
    a_flops = 2.0 * cs * (bm * d if p >= 2 else d) * dv
    a_bytes = (cs * d + cs * dv + cs) * inb
    b_flops = 2.0 * g * cs * (bm * d if p >= 2 else d) * dv
    b_bytes = tile + g * cs * (d + dv) * inb
    a = nmb * nc * (_roof(a_flops, a_bytes) + GRID_STEP_S)
    b = nc * nmb * (_roof(b_flops, b_bytes) + GRID_STEP_S)
    return (a + b) / mega


# ---------------------------------------------------------------------------
# real-hardware measurement
# ---------------------------------------------------------------------------

def measure(key: ShapeKey, sched: Schedule, *, iters: int = 5,
            warmup: int = 2, interpret: bool = False) -> float:
    """Median seconds per call of the compiled kernel under `sched`.

    Builds synthetic inputs at the key's shape (B=1, Hkv=1, Hq=G) and times
    the jitted wrapper with `block_until_ready`. Intended for TPU; passing
    interpret=True times the Python interpreter loop — tests only.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.fastmax_causal import fastmax_causal_pallas
    from repro.kernels.fastmax_causal_bwd import fastmax_causal_bwd_pallas
    from repro.kernels.fastmax_decode import fastmax_decode_pallas
    from repro.kernels.fastmax_noncausal import fastmax_noncausal_pallas

    n, d, dv, g, p = key.n, key.d, key.dv, key.g, key.p
    dtype = jnp.dtype(key.dtype)
    kk = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kk[0], (1, g, max(n, 1), d), dtype)
    k = jax.random.normal(kk[1], (1, 1, max(n, 1), d), dtype)
    v = jax.random.normal(kk[2], (1, 1, max(n, 1), dv), dtype)

    if key.kernel == "causal_fwd":
        fn = lambda: fastmax_causal_pallas(         # noqa: E731
            q, k, v, p=p, chunk_size=sched.chunk_size, interpret=interpret,
            bm=sched.bm, blk=sched.blk, grid=sched.grid)
    elif key.kernel == "hybrid_fwd":
        from repro.kernels.hybrid_causal import hybrid_causal_pallas
        fn = lambda: hybrid_causal_pallas(          # noqa: E731
            q, k, v, p=p, window=min(64, max(n, 1)),
            chunk_size=sched.chunk_size, interpret=interpret,
            bm=sched.bm, blk=sched.blk, grid=sched.grid)
    elif key.kernel == "causal_bwd":
        _, state = fastmax_causal_pallas(
            q, k, v, p=p, chunk_size=sched.chunk_size, interpret=interpret,
            return_state=True)
        do = jax.random.normal(kk[0], (1, g, max(n, 1), dv), dtype)
        fn = lambda: fastmax_causal_bwd_pallas(     # noqa: E731
            q, k, v, state, do, p=p, chunk_size=sched.chunk_size,
            interpret=interpret, bm=sched.bm, blk=sched.blk,
            grid=sched.grid)
    elif key.kernel == "decode":
        from repro.core.decode_state import init_fastmax_state
        state = tuple(init_fastmax_state(1, 1, d, dv, p=p))
        fn = lambda: fastmax_decode_pallas(         # noqa: E731
            q[:, :, :1], k[:, :, :1], v[:, :, :1], state, p=p,
            interpret=interpret, bm=sched.bm, grid=sched.grid)
    else:
        fn = lambda: fastmax_noncausal_pallas(      # noqa: E731
            q, k, v, p=p, chunk_size=sched.chunk_size, interpret=interpret,
            bm=sched.bm, grid=sched.grid)

    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _trace_clean() -> bool:
    """True when no jax trace is active (safe to execute kernels)."""
    from jax._src.core import trace_state_clean
    return trace_state_clean()


# ---------------------------------------------------------------------------
# tuning + cache
# ---------------------------------------------------------------------------

def tune(key: ShapeKey, chunk_size: int = 128, *,
         allow_measure: bool = False):
    """Sweep the candidate set; returns (schedule, source, score).

    Measurement requires allow_measure AND a real TPU AND no active trace;
    everything else scores with the deterministic cost model (ties break on
    candidate order, so the winner is reproducible).
    """
    cands = candidate_schedules(key.kernel, key, chunk_size)
    measured = (allow_measure and key.platform == "tpu"
                and _platform() == "tpu" and _trace_clean())
    best, best_score = None, math.inf
    for sched in cands:
        if measured:
            if cost_model(key, sched) == math.inf:
                continue        # never launch a schedule the model rejects
            try:
                score = measure(key, sched)
            except Exception as e:   # noqa: BLE001 — bad candidate, skip
                print(f"autotune: measure failed for {key_str(key)} "
                      f"{sched}: {type(e).__name__}: {e}", file=sys.stderr)
                continue
        else:
            score = cost_model(key, sched)
        if score < best_score:
            best, best_score = sched, score
    if best is None:    # every candidate infeasible/failed: untuned default
        return (default_schedule(key.kernel, key.d, key.dv, chunk_size),
                "default", math.inf)
    return best, ("measured" if measured else "cost_model"), best_score


_FILE_CACHE: dict = {}   # path -> (mtime, entries)


def load_cache(path: str) -> dict:
    """Entries of the on-disk cache (mtime-memoized; {} when absent)."""
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    hit = _FILE_CACHE.get(path)
    if hit and hit[0] == mtime:
        return hit[1]
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        print(f"autotune: unreadable cache {path} ({e}) — ignoring",
              file=sys.stderr)
        return {}
    if raw.get("version") != CACHE_VERSION:
        print(f"autotune: cache {path} has version {raw.get('version')!r}, "
              f"expected {CACHE_VERSION} — ignoring", file=sys.stderr)
        return {}
    entries = raw.get("entries", {})
    _FILE_CACHE[path] = (mtime, entries)
    return entries


def save_cache(path: str, entries: dict) -> None:
    with open(path, "w") as f:
        json.dump({"version": CACHE_VERSION,
                   "entries": {k: entries[k] for k in sorted(entries)}},
                  f, indent=2)
        f.write("\n")
    _FILE_CACHE.pop(path, None)


def _entry_schedule(entry: dict, key: ShapeKey) -> Optional[Schedule]:
    """Validate + decode a cache entry against the key's shape (a stale
    entry whose blocks no longer divide the dims is treated as a miss)."""
    try:
        s = Schedule(**{f: entry["schedule"][f] for f in Schedule._fields})
    except (KeyError, TypeError):
        return None
    if (key.d % s.bm or key.dv % s.blk or s.chunk_size < 1
            or s.grid not in GRIDS or not tpu_tileable(s, key.d, key.dv)):
        return None
    return s


# provenance: one record per distinct lookup key, snapshot by the
# benchmarks and the dry-run (cleared per cell like registry._LOGGED)
_LOOKUPS: dict = {}
_MISS_MEMO: dict = {}


def clear_lookups() -> None:
    _LOOKUPS.clear()


def snapshot_lookups() -> list:
    return [_LOOKUPS[k] for k in sorted(_LOOKUPS)]


def _record(key: ShapeKey, sched: Schedule, cache: str, source: str):
    _LOOKUPS[key_str(key)] = {
        "kernel": key.kernel,
        "key": key_str(key),
        "schedule": dict(sched._asdict()),
        "cache": cache,      # "hit" | "miss" | "off"
        "source": source,    # "measured" | "cost_model" | "default"
    }


def cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE", DEFAULT_CACHE)


def lookup_schedule(kernel: str, *, n: int, d: int, dv: int, g: int,
                    p: int, dtype, chunk_size: int) -> Optional[Schedule]:
    """The runtime entry point, called by `repro.kernels.ops` per launch.

    Returns None when autotuning is off (the kernels then run their
    untuned `pick_*` defaults — byte-identical to an autotune-free build);
    otherwise the cached or freshly tuned Schedule. Every call records a
    provenance entry regardless of mode.
    """
    mode = autotune_mode()
    key = ShapeKey(kernel, int(n), int(d), int(dv), int(g), int(p),
                   str(jnp_dtype_name(dtype)), _platform())
    if mode == "off":
        _record(key, default_schedule(kernel, d, dv, chunk_size),
                cache="off", source="default")
        return None
    path = cache_path()
    ks = key_str(key)
    entry = load_cache(path).get(ks)
    if entry is not None:
        sched = _entry_schedule(entry, key)
        if sched is not None:
            _record(key, sched, cache="hit",
                    source=entry.get("source", "cost_model"))
            return sched
    memo_key = (mode, path, ks)
    if memo_key in _MISS_MEMO:
        sched, source = _MISS_MEMO[memo_key]
        _record(key, sched, cache="miss", source=source)
        return sched
    sched, source, score = tune(key, chunk_size,
                                allow_measure=(mode == "on"))
    _MISS_MEMO[memo_key] = (sched, source)
    _record(key, sched, cache="miss", source=source)
    if mode == "on" and "REPRO_AUTOTUNE_CACHE" in os.environ:
        # persist like XLA's autotune cache — but only to a path the user
        # explicitly owns; the committed in-repo default is CLI-managed
        entries = dict(load_cache(path))
        entries[ks] = {"schedule": dict(sched._asdict()), "source": source,
                       "score": None if math.isinf(score) else score}
        try:
            save_cache(path, entries)
        except OSError as e:
            print(f"autotune: could not persist to {path} ({e})",
                  file=sys.stderr)
    return sched


def jnp_dtype_name(dtype) -> str:
    import jax.numpy as jnp
    return jnp.dtype(dtype).name


# ---------------------------------------------------------------------------
# gate shapes + CLI (the committed-cache workflow)
# ---------------------------------------------------------------------------

def gate_keys(platform: str = "cpu") -> list:
    """(ShapeKey, chunk_size) for every kernel cell the dryrun-gate and the
    bench-json suite exercise — the shapes the committed cache must cover."""
    from repro.configs import SHAPES, get_config

    out = []
    # bench-json attention_phases shapes (quick / full), f32, p=2
    for n, d, dv, g in ((256, 16, 16, 2), (2048, 64, 64, 2)):
        out += [(ShapeKey("causal_fwd", n, d, dv, g, 2, "float32",
                          platform), 128),
                (ShapeKey("causal_bwd", n, d, dv, g, 2, "float32",
                          platform), 128),
                (ShapeKey("decode", 1, d, dv, g, 2, "float32",
                          platform), 128),
                (ShapeKey("noncausal", n, d, dv, g, 2, "float32",
                          platform), 128),
                (ShapeKey("hybrid_fwd", n, d, dv, g, 2, "float32",
                          platform), 128)]
    # dryrun-gate kernel cells: qwen2.5-32b at TP=16 routes feature mode
    # (hkv=8 does not divide 16; Dv does), so the per-device launches see
    # the LOCAL Dv shard; q/k stay replicated at full head_dim
    cfg = get_config("qwen2.5-32b")
    tp = 16
    d = cfg.head_dim
    dvl = cfg.head_dim // tp
    g = cfg.n_heads // cfg.n_kv_heads
    dt = "bfloat16" if cfg.activ_dtype == "bfloat16" else "float32"
    n_train = SHAPES["train_4k"].seq_len
    out += [(ShapeKey("causal_fwd", n_train, d, dvl, g, 2, dt, platform),
             128),
            (ShapeKey("causal_bwd", n_train, d, dvl, g, 2, dt, platform),
             128),
            (ShapeKey("decode", 1, d, dvl, g, 2, dt, platform), 128),
            # hybrid train_4k cell: the feature-mode forward launches see
            # the same local Dv shard; the backward is the jnp band scan
            # (no kernel), so only hybrid_fwd needs an entry
            (ShapeKey("hybrid_fwd", n_train, d, dvl, g, 2, dt, platform),
             128)]
    return out


def build_gate_entries(platform: str = "cpu") -> dict:
    """Cost-model winners for every gate shape (deterministic on any host)."""
    entries = {}
    for key, chunk in gate_keys(platform):
        sched, source, score = tune(key, chunk, allow_measure=False)
        entries[key_str(key)] = {
            "schedule": dict(sched._asdict()),
            "source": source,
            "score": None if math.isinf(score) else score,
        }
    return entries


def main() -> None:
    ap = argparse.ArgumentParser(
        description="fastmax kernel schedule autotuner (committed-cache "
                    "workflow; runtime tuning is env-driven, see module "
                    "docstring)")
    ap.add_argument("--cache", default=DEFAULT_CACHE,
                    help="cache file (default: the committed in-repo one)")
    ap.add_argument("--platform", default="cpu",
                    help="platform tag for the generated entries")
    g = ap.add_mutually_exclusive_group(required=True)
    g.add_argument("--write", action="store_true",
                   help="retune the gate shapes (cost model) and write "
                        "them into the cache, preserving other entries")
    g.add_argument("--check", action="store_true",
                   help="fail if the committed cache is stale vs a fresh "
                        "cost-model sweep (schema or winner drift)")
    args = ap.parse_args()

    fresh = build_gate_entries(args.platform)
    if args.write:
        entries = dict(load_cache(args.cache))
        entries.update(fresh)
        save_cache(args.cache, entries)
        print(f"autotune: wrote {len(fresh)} gate entries "
              f"({len(entries)} total) to {args.cache}")
        return

    drift = []
    try:
        with open(args.cache) as f:
            raw = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(f"autotune --check: cannot read {args.cache}: {e}")
    if raw.get("version") != CACHE_VERSION:
        drift.append(f"schema version {raw.get('version')!r} != "
                     f"{CACHE_VERSION}")
    committed = raw.get("entries", {})
    for ks, entry in fresh.items():
        have = committed.get(ks)
        if have is None:
            drift.append(f"missing entry: {ks}")
        elif have.get("schedule") != entry["schedule"]:
            drift.append(f"winner drift: {ks}: committed "
                         f"{have.get('schedule')} != fresh "
                         f"{entry['schedule']}")
    if drift:
        for line in drift:
            print(f"autotune --check: STALE — {line}")
        raise SystemExit(
            f"autotune --check: {len(drift)} stale entr"
            f"{'y' if len(drift) == 1 else 'ies'} — regenerate with "
            f"`make autotune` and commit the cache")
    print(f"autotune --check: OK ({len(fresh)} gate entries up to date "
          f"in {args.cache})")


if __name__ == "__main__":
    main()
