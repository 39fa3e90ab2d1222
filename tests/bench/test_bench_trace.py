"""The trace reduction on a small trace recorded on the CPU: busy and idle
time over the traced window, the top device operations, a named op's
events, and idle gaps labelled by the bench span that covers them."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_cell  # noqa: E402,F401  (puts the repo and src on sys.path)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.engine.step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    from bench import trace as T
    return T.load(T.find_xplane(d))


def test_window_busy_and_idle(recorded):
    from bench import trace as T
    lo, hi = recorded.window
    busy = T.busy_seconds(recorded)
    assert hi - lo >= 0.15
    assert 0 < busy < 0.5 * (hi - lo)
    n, secs = T.span_seconds(recorded, "bench.engine.step")
    assert n == 3 and 0 < secs < hi - lo


def test_top_ops_and_kernel_events(recorded):
    from bench import trace as T
    top = T.top_ops(recorded)
    assert top and all(sec > 0 for _, sec in top)
    assert len(top) <= 10
    evs = T.kernel_events(recorded, "dot_general")
    assert len(evs) >= 3
    assert not T.kernel_events(recorded, "no_such_kernel")


def test_idle_gaps_labelled_by_host_span(recorded):
    from bench import trace as T
    gaps = T.idle_gaps(recorded)
    assert gaps[0][0] == "bench.wait"
    assert gaps[0][1] >= 0.04
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_missing_window_span_is_an_error(recorded):
    from bench import trace as T
    tr = T.Trace(recorded.device, [s for s in recorded.spans
                                   if s.name != T.WINDOW_SPAN])
    with pytest.raises(ValueError):
        tr.window
