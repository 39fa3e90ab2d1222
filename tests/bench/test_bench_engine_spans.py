"""The trace reduction on a trace recorded around a real serving engine,
driven the way bench/serve.py drives it: the engine's own `serve.*` spans
nest inside each `bench.engine.step`. `load` keeps host spans under
`bench.` only, so every existing reader reads the same number with or
without the engine's spans; given those spans, `idle_gaps` labels a gap
by the innermost engine phase that covers it."""
import argparse
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cell  # noqa: E402,F401  (puts the repo and src on sys.path)

from bench import trace as T  # noqa: E402


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """(the loaded trace, the serve.* spans read from the same file)."""
    import dataclasses

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from repro.attention import AttentionSpec
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(get_smoke_config("qwen3-1.7b"),
                              attn=AttentionSpec.parse("fastmax2-chunked"))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, max_slots=2, max_len=64, chunk=8)
    rng = np.random.default_rng(3)
    for n in (12, 20):
        eng.submit(rng.integers(0, 256, n).astype(np.int32), 6)
    eng.step()                                   # compile outside the trace
    d = str(tmp_path_factory.mktemp("engine-trace"))
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.traced"):
        while eng.pending:
            with jax.profiler.TraceAnnotation("bench.engine.step"):
                eng.step()
    jax.profiler.stop_trace()
    path = T.find_xplane(d)
    serve = [T.Event(ev.name, ev.start_ns * 1e-9,
                     (ev.start_ns + ev.duration_ns) * 1e-9)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")]
    return T.load(path), serve


def test_engine_spans_leave_existing_readers_unchanged(engine_trace):
    from bench import readers
    tr, serve = engine_trace
    assert serve and all(s.name.startswith("bench.") for s in tr.spans)
    both = T.Trace(tr.device, tr.spans + serve)
    r = dict(peak={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}, chips=1,
             model_flops=1e9, step_span="bench.engine.step", kernel_calls=[])
    a = argparse.Namespace(trace=tr, **r)
    b = argparse.Namespace(trace=both, **r)
    assert readers.idle_share(a) == readers.idle_share(b)
    assert readers.mfu(a) == readers.mfu(b) is not None
    assert T.span_seconds(tr, "bench.engine.step") == \
        T.span_seconds(both, "bench.engine.step")
    assert T.top_ops(tr) == T.top_ops(both)
    assert T.kernel_events(tr, "dot_general") == \
        T.kernel_events(both, "dot_general")


def test_idle_gaps_fall_under_the_innermost_engine_phase(engine_trace):
    tr, serve = engine_trace
    both = T.Trace(tr.device, tr.spans + serve)
    phases = [g[0] for g in T.idle_gaps(both)]
    harness = [g[0] for g in T.idle_gaps(tr)]
    assert sum(lb.startswith("serve.") for lb in phases) > len(phases) // 2
    assert "bench.engine.step" in harness
    assert not any(lb.startswith("serve.") for lb in harness)
