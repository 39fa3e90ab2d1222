"""The serving engine's profiler spans and tick counters (`serve` marker).

A tiny engine driven under `jax.profiler` records one `serve.step` per
tick, with its phases nested inside it in order; it serves the same tokens
as with the profiler off; and `stats()` counts the ticks that had a
prefill part and those that had a decode part.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.attention import AttentionSpec
from repro.configs import get_smoke_config
from repro.models import init_model
from repro.serve import ServeEngine

pytestmark = pytest.mark.serve

PHASES = ("serve.admit", "serve.schedule", "serve.launch", "serve.wait",
          "serve.emit")


def _engine():
    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"),
                              attn=AttentionSpec.parse("fastmax2-chunked"))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg, max_slots=2, max_len=64, chunk=8)


def _serve(eng, prompts):
    """Serve the prompts (the third waits for a free slot); returns the
    tokens of each and the (do_prefill, do_decode) of every launch."""
    kinds = []
    tick = eng._tick_fn

    def record(*args, **kw):
        kinds.append((kw["do_prefill"], kw["do_decode"]))
        return tick(*args, **kw)

    eng._tick_fn = record
    rids = [eng.submit(p, 5) for p in prompts]
    outs = eng.run()
    return [outs[r] for r in rids], kinds


def _serve_spans(trace_dir):
    """[(name, start_ns, end_ns, step_num)] of the host events named
    serve.*, by start."""
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    step = dict(ev.stats).get("step_num")
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, step))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (19, 8, 12)]
    plain, _ = _serve(_engine(), prompts)          # also compiles the tick
    eng = _engine()
    d = str(tmp_path_factory.mktemp("serve-trace"))
    jax.profiler.start_trace(d)
    try:
        toks, kinds = _serve(eng, prompts)
    finally:
        jax.profiler.stop_trace()
    return eng, plain, toks, kinds, _serve_spans(d)


def test_every_tick_has_one_step_span_with_its_phases_in_order(traced):
    eng, _, _, kinds, spans = traced
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == eng.tick_count > 0
    assert [s[3] for s in steps] == list(range(1, eng.tick_count + 1))
    launched = 0
    for name, lo, hi, _ in steps:
        kids = [s for s in spans if s[0] != "serve.step"
                and lo <= s[1] < hi]
        assert all(s[2] <= hi for s in kids)                 # nested
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))  # in order
        names = tuple(s[0] for s in kids)
        assert names in (PHASES, PHASES[:2])
        launched += names == PHASES
    assert launched == len(kinds)


def test_tokens_match_the_run_with_the_profiler_off(traced):
    _, plain, toks, _, _ = traced
    for a, b in zip(plain, toks):
        np.testing.assert_array_equal(a, b)


def test_prefill_and_decode_ticks_are_counted(traced):
    eng, _, toks, kinds, _ = traced
    st = eng.stats()
    assert st["prefill_ticks"] == sum(p for p, _ in kinds)
    assert st["decode_ticks"] == sum(d for _, d in kinds)
    assert st["prefill_ticks"] > 0 and st["decode_ticks"] > 0
    assert st["decode_tokens"] <= st["decode_ticks"] * st["slots_total"]
    # every token but each request's first comes from a decode part
    assert st["decode_tokens"] == sum(len(t) - 1 for t in toks)
