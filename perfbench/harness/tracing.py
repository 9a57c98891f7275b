"""Spans of the benchmark's own loop, and the reduction of a profiler trace
to device busy time, time per device program and idle gaps by host span.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

import jax

SPAN_PREFIX = "bench."
#: the span around the whole traced window
WINDOW_SPAN = "bench.window"
#: device planes of the chips; lines holding one event per operation and
#: one per device program
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: how many earlier spans to look through for one that covers a gap (the
#: loop's spans nest at most a few deep and never overlap otherwise)
SPAN_DEPTH = 8


def span(name: str):
    """A host span on the profiler's clock (written while a trace is open,
    free otherwise); the trace's idle gaps are put to these spans."""
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# trace capture
# ---------------------------------------------------------------------------


def start(log_dir: str) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # host Python calls: cost, no metric
    opts.host_tracer_level = 1     # annotations only
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    jax.profiler.stop_trace()


def newest_xplane(log_dir: str) -> str | None:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(path: str) -> list[Event]:
    """Events of the device planes' op and program lines, and the host's
    benchmark spans, from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not on_device and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def merge(intervals):
    """Union of [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def module_name(name: str) -> str:
    """A device program's name without the id XLA appends, e.g.
    ``jit_train_step(123)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                 # mean over the chips
    devices: int
    modules: dict = field(default_factory=dict)   # name -> (count, seconds)
    idle_gaps: dict = field(default_factory=dict)  # host span -> seconds

    def module(self, pattern: str) -> tuple[int, float]:
        """(count, seconds) of the device programs whose name holds
        ``pattern``."""
        n, s = 0, 0.0
        for name, (c, sec) in self.modules.items():
            if pattern in name:
                n, s = n + c, s + sec
        return n, s


def reduce(events: list[Event]) -> TraceSummary | None:
    """Busy share, programs and idle gaps inside the benchmark's window
    span.  None when the trace holds no device plane."""
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})
    if not planes:
        return None
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if windows:
        lo = min(w.start_ns for w in windows)
        hi = max(w.end_ns for w in windows)
    else:
        dev = [e for e in events if e.plane in planes]
        lo, hi = min(e.start_ns for e in dev), max(e.end_ns for e in dev)
    busy = []
    first_busy = None
    for p in planes:
        ops = [(e.start_ns, e.end_ns) for e in events
               if e.plane == p and e.line == OPS_LINE]
        if not ops:   # a trace without per-op events: whole programs
            ops = [(e.start_ns, e.end_ns) for e in events
                   if e.plane == p and e.line == MODULES_LINE]
        u = merge(_clip(ops, lo, hi))
        busy.append(sum(e - s for s, e in u))
        if first_busy is None:
            first_busy = u
    modules: dict = {}
    for e in events:
        if e.plane in planes and e.line == MODULES_LINE:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t <= s:
                continue
            name = module_name(e.name)
            c, sec = modules.get(name, (0, 0.0))
            modules[name] = (c + 1, sec + (t - s) / 1e9)
    # idle gaps of the first chip, each put to the innermost host span
    # that covers its middle ("none" when the host was outside every span)
    spans = sorted(((e.start_ns, e.end_ns, e.name) for e in events
                    if e.plane not in planes and e.name != WINDOW_SPAN
                    and e.name.startswith(SPAN_PREFIX)))
    starts = [a for a, _, _ in spans]
    gaps: dict = {}
    prev = lo
    for s, e in first_busy + [[hi, hi]]:
        if s > prev:
            mid = (prev + s) / 2
            owner = "none"
            # latest-starting span that covers mid = the innermost one
            i = bisect.bisect_right(starts, mid) - 1
            for j in range(i, max(i - SPAN_DEPTH, -1), -1):
                if spans[j][1] > mid:
                    owner = spans[j][2]
                    break
            gaps[owner] = gaps.get(owner, 0.0) + (s - prev) / 1e9
        prev = max(prev, e)
    return TraceSummary(window_s=(hi - lo) / 1e9,
                        busy_s=sum(busy) / len(busy) / 1e9,
                        devices=len(planes), modules=modules, idle_gaps=gaps)


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    ops = sorted(((n, sec) for n, (c, sec) in summary.modules.items()),
                 key=lambda x: -x[1])[:top]
    gaps = sorted(summary.idle_gaps.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
