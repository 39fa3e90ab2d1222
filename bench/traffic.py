"""The one traffic generator: a mix file of parameters in, requests out.

A mix is `traffic/<name>.json`. Serving mixes (`"kind": "serve"`) give the
prompt and output length distributions, the loop (closed, with a number of
clients) and how many requests to draw.
Training mixes (`"kind": "train"`) give the sequence length, the batch and
how many distinct batches to draw.

Every seed gets the same set of sizes: the distribution's quantiles at
(i + 1/2)/n; the seed only shuffles their order and draws the token ids.
So a seed changes which requests meet which, not how much work a run
holds.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray        # int32 token ids
    max_new: int              # output tokens asked for


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths: a lognormal's quantiles (median, sigma), clipped to
    [min, max]."""
    if spec.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def serve_requests(mix: dict, seed: int, vocab: int) -> list:
    """The run's requests, in submission order."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = int(mix["requests"])
    rng = np.random.default_rng(seed)
    plen = rng.permutation(quantile_lengths(mix["prompt"], n))
    olen = rng.permutation(quantile_lengths(mix["output"], n))
    return [Request(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(plen, olen)]


def train_batches(mix: dict, seed: int, vocab: int) -> list:
    """`batches` distinct (tokens, targets) pairs, each [batch, seq] int32:
    Zipf-distributed ids (exponent `zipf_a`), the targets being the
    tokens shifted by one."""
    rng = np.random.default_rng(seed)
    b, n = int(mix["batch"]), int(mix["seq"])
    out = []
    for _ in range(int(mix["batches"])):
        ids = (rng.zipf(float(mix["zipf_a"]), size=(b, n + 1)) - 1) % vocab
        ids = ids.astype(np.int32)
        out.append((ids[:, :-1], ids[:, 1:]))
    return out


def percentile(values, q: float) -> float:
    """The q-th percentile by nearest rank (the smallest value with at
    least q% of the values at or below it)."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])
