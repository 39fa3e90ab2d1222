"""Reduction of a profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read: device busy and idle time over the traced window,
each kernel's device time and call count, the top device operations, and
the longest idle gaps labelled by the harness's host span that covers
them.

Device operations are the events of the "XLA Ops" lines of the
`/device:TPU:<n>` planes. Where a trace has no TPU plane (a CPU trace in
the tests), the host events that carry an `hlo_op` stat stand in for them.
Host spans are the harness's own `TraceAnnotation`s, named "bench.*"; the
span "bench.traced" marks the traced window.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds
    end: float
    text: str = ""        # the event's name and string stats, for matching


@dataclasses.dataclass
class Trace:
    device: dict          # device id -> [Event] (operations)
    spans: list           # [Event] host spans named bench.*

    @property
    def window(self):
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError(f"no {WINDOW_SPAN} span in the trace")
        return w[-1].start, w[-1].end


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def _stat_text(ev) -> str:
    parts = [ev.name]
    for _, v in ev.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device = collections.defaultdict(list)
    spans, cpu_ops = [], []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if m is not None:
                    if line.name == "XLA Ops":
                        device[int(m.group(1))].append(
                            Event(ev.name, s, e, _stat_text(ev)))
                elif plane.name.startswith("/host:"):
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(ev.name, s, e))
                    elif any(k == "hlo_op" for k, _ in ev.stats):
                        cpu_ops.append(Event(ev.name, s, e, _stat_text(ev)))
    if not device and cpu_ops:
        device[0] = cpu_ops
    return Trace(dict(device), spans)


def _clip(events, lo, hi):
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(tr: Trace) -> float:
    """Seconds of the window in which some operation ran, averaged over
    the devices in the trace."""
    lo, hi = tr.window
    per = [sum(e - s for s, e in _union(_clip(evs, lo, hi)))
           for evs in tr.device.values()]
    return sum(per) / len(per) if per else 0.0


def kernel_events(tr: Trace, name: str) -> list:
    """Device events of the kernel `name` inside the window."""
    lo, hi = tr.window
    pat = re.compile(re.escape(name) + r"(?![0-9A-Za-z_])")
    return [e for evs in tr.device.values() for e in evs
            if e.start >= lo and e.end <= hi and pat.search(e.text)]


_CONTAINER = re.compile(r"^%?(while|call|conditional)[.\d]*$")


def op_name(event_name: str) -> str:
    """An HLO op's instruction name without its number: the device events
    carry the whole instruction text ("%fusion.12 = f32[...] fusion(...)")."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def top_ops(tr: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the device operations with the most time in
    the window, numbered instances (fusion.12) summed under one name.
    Control flow (a while loop, a call) holds other ops and is left out."""
    lo, hi = tr.window
    acc = collections.Counter()
    for evs in tr.device.values():
        for e in evs:
            head = e.name.split(" = ", 1)[0]
            if e.end > lo and e.start < hi and not _CONTAINER.match(head):
                acc[op_name(e.name)] += min(e.end, hi) - max(e.start, lo)
    return [[k, v] for k, v in acc.most_common(n)]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """[[host span, seconds]] of the longest gaps in which no operation
    ran on the device, each labelled by the innermost bench.* span that
    covers the gap's middle ("none" where no span does)."""
    lo, hi = tr.window
    gaps = []
    for evs in tr.device.values():
        busy = _union(_clip(evs, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    inner = [sp for sp in tr.spans if sp.name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        cover = [sp for sp in inner if sp.start <= mid <= sp.end]
        label = min(cover, key=lambda sp: sp.end - sp.start).name \
            if cover else "none"
        out.append([label, e - s])
    return out


def span_seconds(tr: Trace, name: str) -> tuple:
    """(count, summed seconds) of the host spans `name` inside the
    window."""
    lo, hi = tr.window
    sel = [sp for sp in tr.spans
           if sp.name == name and sp.start >= lo and sp.end <= hi]
    return len(sel), sum(sp.end - sp.start for sp in sel)
