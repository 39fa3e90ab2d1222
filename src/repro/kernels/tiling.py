"""Shared tiling policy for the fastmax m-blocked degree-2 contractions.

Two independent blockings of the degree-2 moment `m2 [D·D, Dv]` (m-major):

* `pick_bm` — the ROW (first-moment-index) streaming block. Both the jnp
  chunked scan (`repro.core.fastmax`) and the Pallas kernels slice the
  working tile to [bm*D, Dv] so the per-step intermediates are [*, bm*D].
  bm is the largest divisor of D whose flattened row count bm*D stays
  under a budget: ~512 rows for VMEM-resident kernel tiles (MXU-friendly
  inner matmuls), ~2048 for the XLA scan path (bounds the [..., N, bm*D]
  intermediate that the naive einsum would blow up to [..., N, D, Dv]).

* `pick_blk` — the COLUMN (value-feature, Dv) carry block. The causal
  forward/backward kernels hold the RUNNING moment carry in VMEM scratch
  and can tile its Dv axis into `nb = Dv/blk` independent column blocks (a
  grid axis): per-block scratch is D²·blk·4 bytes, the chunk forward is
  recomputed once per block from the reversible carry, and every emitted
  quantity either slices (o, dv, the m-moments) or sums (dq, dk — the
  contractions over Dv are linear in the per-block cotangents) across
  blocks. A block is the minor (lane) dim of its v/o/moment tiles, so the
  TPU can only tile widths that equal Dv or are multiples of 128
  (`lane_tileable`). blk is the largest tileable divisor of Dv with D²·blk
  at most the budget — 2M f32 words (8 MB) for the forward's single tuple,
  1M for each tuple of the backward's carry + cotangent pair — or, when
  none fits, the smallest tileable one. At D = Dv = 128 that is blk = 128
  (nb = 1) for both: the backward's two 8 MB tuples exceed the compiler's
  DEFAULT scoped-VMEM limit (16 MiB), not the chip's VMEM (128 MiB per
  v5e core), so the carry kernels raise the limit to `VMEM_LIMIT_BYTES`.

Both pickers are the UNTUNED defaults: the schedule autotuner
(`repro.kernels.autotune`) sweeps bm/blk (among other knobs) per shape and
overrides them when enabled; it also calls these per candidate inside the
sweep loop, so they enumerate divisors in O(sqrt(d)) instead of scanning
every integer up to d.
"""
from __future__ import annotations

import functools

__all__ = ["pick_bm", "pick_blk", "divisors", "lane_tileable",
           "KERNEL_BM_BUDGET", "SCAN_BM_BUDGET", "FWD_BLK_BUDGET",
           "BWD_BLK_BUDGET", "VMEM_LIMIT_BYTES"]

KERNEL_BM_BUDGET = 512   # Pallas VMEM tiles
SCAN_BM_BUDGET = 2048    # jnp chunked-scan intermediates

FWD_BLK_BUDGET = 2 << 20   # f32 words per degree-2 carry tuple (1 tuple)
BWD_BLK_BUDGET = 1 << 20   # f32 words per tuple (carry + cotangent pair)

# Scoped-VMEM limit of the kernels. The compiler's default is 16 MiB; a
# v5e core has 128 MiB. The 128×128 backward holds two 8 MiB scratch tuples
# plus single-buffered 8 MiB state blocks (~40 MiB in all), so the kernels
# ask for 96 MiB and leave the rest to Mosaic's internal scratch.
VMEM_LIMIT_BYTES = 96 << 20

LANES = 128   # TPU vreg lane width: the minor dim of a block tiles by this


@functools.lru_cache(maxsize=None)
def divisors(n: int) -> tuple:
    """All divisors of `n`, ascending (n >= 1)."""
    if not isinstance(n, int) or n < 1:
        raise ValueError(f"divisors() needs a positive int, got {n!r}")
    small, large = [], []
    i = 1
    while i * i <= n:
        if n % i == 0:
            small.append(i)
            if i != n // i:
                large.append(n // i)
        i += 1
    return tuple(small + large[::-1])


def _check_budget(budget) -> int:
    if not isinstance(budget, int) or budget < 1:
        raise ValueError(f"budget must be a positive int, got {budget!r}")
    return budget


@functools.lru_cache(maxsize=None)
def pick_bm(d: int, budget: int = KERNEL_BM_BUDGET) -> int:
    """Largest divisor of `d` with bm*d <= budget (always >= 1)."""
    _check_budget(budget)
    best = 1
    for bm in divisors(d):
        if bm * d <= budget:
            best = bm   # divisors ascend, so the last feasible is largest
    return best


def lane_tileable(width: int, full: int) -> bool:
    """Whether a block `width` wide along a minor dim of size `full` obeys
    the TPU's (8, 128) tiling rule: the whole dim, or a multiple of 128."""
    return width == full or width % LANES == 0


@functools.lru_cache(maxsize=None)
def pick_blk(d: int, dv: int, budget: int = FWD_BLK_BUDGET) -> int:
    """Largest lane-tileable divisor of `dv` with d*d*blk <= budget; the
    smallest tileable one when none fits (dv itself below 128 lanes).

    The Dv carry-block of the causal kernels: one degree-2 scratch tuple
    is d*d*blk f32 words per grid program. blk == dv means nb == 1 — the
    unblocked schedule.
    """
    _check_budget(budget)
    if not isinstance(d, int) or d < 1:
        raise ValueError(f"d must be a positive int, got {d!r}")
    tileable = [blk for blk in divisors(dv) if lane_tileable(blk, dv)]
    fits = [blk for blk in tileable if d * d * blk <= budget]
    return fits[-1] if fits else tileable[0]
