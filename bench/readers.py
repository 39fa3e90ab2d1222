"""What the per-layer metric readers share. Each reader (`metrics/<name>.py`)
takes the run's readings `r` (the reduced trace, the device's peaks, the
chips, the kernels' calls from the timed programs, the model FLOPs of the
traced window and the host span of one tick or step) and returns its
number, or None where the run has nothing for it to read."""
from __future__ import annotations

from bench import counts
from bench import trace as T
from bench.device import say


def idle_share(r):
    """Percent of the traced window in which no operation ran on the
    device, averaged over the chips."""
    lo, hi = r.trace.window
    return 100.0 * (1.0 - T.busy_seconds(r.trace) / (hi - lo))


def mfu(r):
    """Model FLOPs of the ticks or steps in the traced window over the
    sum of their host spans, over chips times the bf16 peak, in percent."""
    if not r.model_flops or not r.step_span:
        return None
    n, secs = T.span_seconds(r.trace, r.step_span)
    if not n or secs <= 0:
        return None
    return 100.0 * r.model_flops / (secs * r.chips * r.peak["bf16_flops"])


def roofline(r, kernel):
    """The least time the chip could take for the kernel's calls in the
    traced window (per call the larger of operations over peak FLOP/s and
    bytes over peak bytes/s) over their summed device time, in percent.
    Where the timed programs call the kernel with several shapes, each
    call is given the cheapest of them."""
    evs = T.kernel_events(r.trace, kernel)
    costs = [counts.kernel_cost(name, ops, res)
             for name, ops, res in r.kernel_calls if name == kernel]
    if not evs or not costs:
        return None
    flops_s, bytes_s = r.peak["bf16_flops"], r.peak["hbm_bytes_per_s"]
    ops, nb = min(costs, key=lambda c: max(c[0] / flops_s, c[1] / bytes_s))
    t_ops, t_bytes = ops / flops_s, nb / bytes_s
    busy = sum(e.end - e.start for e in evs)
    say(f"{kernel}: {len(evs)} calls, {1e3 * busy:.3f} ms on the device; per "
        f"call {ops:.4g} ops, {nb:.4g} bytes: "
        f"{'compute' if t_ops >= t_bytes else 'memory'} bound")
    return 100.0 * len(evs) * max(t_ops, t_bytes) / busy
