"""The program's own stage spans (``veloc.*``, written by
``repro.core.spans``) in a profiler trace: for each span name its count,
total and self seconds and bytes; the device's idle gaps put to the
innermost program span open at their middle; and the per-stage readings
of the save and the restore, per save or per recovery.

Every Python thread is its own line of the ``/host:CPU`` plane and every
such line has the same name, so a thread is told apart by the line's
position in its plane.  A span's parent is the span open around it on the
same thread; a stage belongs to a request when it lies inside the
interval of the request's root span, on any thread.

    cd perfbench && python -m harness.stages <trace.xplane.pb>

prints ``summary`` of one trace as JSON.
"""
from __future__ import annotations

import bisect
import json
import sys
from dataclasses import dataclass, field

from harness import tracing

PREFIX = "veloc."
#: benchmark spans counted as one save or one recovery each
SAVE_SPAN = "bench.save"
RECOVERY_SPAN = "bench.recovery"

#: metric -> (span, root span it lies in, self time?, counted per): the
#: stage readings of the save (``.protect``) and of the restore
#: (``.resume``).  Self time leaves out the child spans on the same thread
#: (serialize: its D2H and digests; decode: its digest; place: its
#: device_put).
METRICS = {
    "d2h_s.protect": ("veloc.d2h", "veloc.pipeline", False, SAVE_SPAN),
    "serialize_s.protect": ("veloc.serialize", "veloc.pipeline", True,
                            SAVE_SPAN),
    "digest_s.protect": ("veloc.digest", "veloc.pipeline", False, SAVE_SPAN),
    "l1_put_s.protect": ("veloc.l1-local", "veloc.pipeline", False,
                         SAVE_SPAN),
    "l3_flush_s.protect": ("veloc.l3-flush", "veloc.pipeline", False,
                           SAVE_SPAN),
    "restore_fetch_s.resume": ("veloc.tier.get", "veloc.restore", True,
                               RECOVERY_SPAN),
    "restore_verify_s.resume": ("veloc.digest", "veloc.restore", False,
                                RECOVERY_SPAN),
    "restore_decode_s.resume": ("veloc.restore.decode", "veloc.restore",
                                True, RECOVERY_SPAN),
    "restore_assemble_s.resume": ("veloc.restore.place", "veloc.restore",
                                  True, RECOVERY_SPAN),
    "restore_device_put_s.resume": ("veloc.restore.device_put",
                                    "veloc.restore", False, RECOVERY_SPAN),
}
#: device idle seconds while a save's pipeline is open, per save
SAVE_IDLE = "save_idle_s.train"


@dataclass
class HostSpan:
    line: tuple          # (plane, index of the line in the plane)
    name: str
    start_ns: float
    end_ns: float
    stats: dict = field(default_factory=dict)
    self_ns: float = 0.0  # within the window, less its children's

    def covers(self, other: "HostSpan") -> bool:
        return self.start_ns <= other.start_ns and \
            other.end_ns <= self.end_ns

    def within(self, lo: float, hi: float) -> float:
        """Nanoseconds of the span inside [lo, hi)."""
        return max(0.0, min(self.end_ns, hi) - max(self.start_ns, lo))


@dataclass
class Stages:
    lo: float                  # the traced window, ns
    hi: float
    spans: list                # HostSpan: veloc.* and bench.*
    idle: list                 # [start, end) device idle intervals, ns
    counts: dict               # SAVE_SPAN / RECOVERY_SPAN -> how many

    @property
    def program(self) -> dict:
        """span name -> {count, total_s, self_s, bytes} within the
        window."""
        out: dict = {}
        for s in self.spans:
            if not s.name.startswith(PREFIX):
                continue
            dur = s.within(self.lo, self.hi)
            if dur <= 0:
                continue
            p = out.setdefault(s.name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0, "bytes": 0})
            p["count"] += 1
            p["total_s"] += dur / 1e9
            p["self_s"] += s.self_ns / 1e9
            p["bytes"] += int(s.stats.get("bytes", 0))
        return out

    @property
    def program_gaps(self) -> dict:
        """Device idle seconds by the innermost program span open at each
        gap's middle on any thread ("none" outside every one)."""
        spans = [s for s in self.spans if s.name.startswith(PREFIX)]
        starts = [s.start_ns for s in spans]
        out: dict = {}
        for a, b in self.idle:
            mid = (a + b) / 2
            owner = "none"
            for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                if spans[j].end_ns > mid:   # latest-starting = innermost
                    owner = spans[j].name
                    break
            out[owner] = out.get(owner, 0.0) + (b - a) / 1e9
        return out

    def seconds(self, name: str, root: str, self_time: bool) -> float:
        """Seconds of the spans ``name`` that lie inside a ``root`` span,
        on any thread, total or self, within the window."""
        roots = [s for s in self.spans if s.name == root]
        total = 0.0
        for s in self.spans:
            if s.name != name or not any(r.covers(s) for r in roots):
                continue
            total += s.self_ns if self_time else s.within(self.lo, self.hi)
        return total / 1e9

    def idle_inside(self, root: str) -> float:
        """Device idle seconds while a ``root`` span is open."""
        open_ = tracing.merge((s.start_ns, s.end_ns) for s in self.spans
                              if s.name == root)
        total = 0.0
        for a, b in self.idle:
            for s, e in open_:
                total += max(0.0, min(b, e) - max(a, s))
        return total / 1e9

    def metric(self, name: str, times: int):
        """One stage reading per save or recovery, over ``times`` of them;
        None when the trace holds no such span."""
        if not times:
            return None
        if name == SAVE_IDLE:
            if not any(s.name == "veloc.pipeline" for s in self.spans):
                return None
            return self.idle_inside("veloc.pipeline") / times
        span, root, self_time, _ = METRICS[name]
        if not any(s.name == span for s in self.spans):
            return None
        return self.seconds(span, root, self_time) / times


def _self_times(spans: list, lo: float, hi: float) -> None:
    """Each span's time within the window less the part its direct
    children on the same thread cover (spans on one thread nest)."""
    by_line: dict = {}
    for s in spans:
        by_line.setdefault(s.line, []).append(s)
    for line in by_line.values():
        line.sort(key=lambda s: (s.start_ns, -s.end_ns))
        stack: list = []
        for s in line:
            s.self_ns = s.within(lo, hi)
            while stack and not stack[-1].covers(s):
                stack.pop()
            if stack:
                stack[-1].self_ns -= s.self_ns
            stack.append(s)


def host_spans(path: str) -> list:
    """The ``veloc.*`` and ``bench.*`` spans of a trace's host planes,
    each with its thread and its stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((PREFIX, tracing.SPAN_PREFIX)):
                    out.append(HostSpan(
                        (plane.name, i), e.name, float(e.start_ns),
                        float(e.start_ns) + float(e.duration_ns),
                        dict(e.stats)))
    return out


def reduce(spans: list, events: list):
    """``Stages`` of a trace: host spans from ``host_spans`` and the
    device events from ``tracing.load_events``.  None without a device
    plane.  The window and the device's busy time are taken as
    ``tracing.reduce`` takes them: the benchmark's window span, the first
    chip's op line."""
    planes = sorted({e.plane for e in events
                     if tracing.DEVICE_PLANE.match(e.plane)})
    if not planes:
        return None
    windows = [s for s in spans if s.name == tracing.WINDOW_SPAN]
    if windows:
        lo = min(w.start_ns for w in windows)
        hi = max(w.end_ns for w in windows)
    else:
        dev = [e for e in events if e.plane in planes]
        lo, hi = min(e.start_ns for e in dev), max(e.end_ns for e in dev)
    first = [e for e in events if e.plane == planes[0]]
    ops = [(e.start_ns, e.end_ns) for e in first
           if e.line == tracing.OPS_LINE] or \
        [(e.start_ns, e.end_ns) for e in first
         if e.line == tracing.MODULES_LINE]
    idle, prev = [], lo
    for s, e in tracing.merge(tracing._clip(ops, lo, hi)) + [[hi, hi]]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    spans = sorted(spans, key=lambda s: s.start_ns)
    _self_times([s for s in spans if s.name.startswith(PREFIX)], lo, hi)
    counts = {n: sum(1 for s in spans if s.name == n
                     and lo <= s.start_ns < hi)
              for n in (SAVE_SPAN, RECOVERY_SPAN)}
    return Stages(lo=lo, hi=hi, spans=spans, idle=idle, counts=counts)


def summary(path: str) -> dict:
    """Program spans, idle gaps by program span and every stage reading
    of one trace file."""
    st = reduce(host_spans(path), tracing.load_events(path))
    if st is None:
        return {}
    readings = {SAVE_IDLE: st.metric(SAVE_IDLE, st.counts[SAVE_SPAN])}
    for name, (_, _, _, per) in METRICS.items():
        readings[name] = st.metric(name, st.counts[per])
    return {"window_s": (st.hi - st.lo) / 1e9, "counts": st.counts,
            "program": st.program, "program_gaps": st.program_gaps,
            "metrics": {k: v for k, v in readings.items() if v is not None}}


if __name__ == "__main__":
    print(json.dumps(summary(sys.argv[1]), indent=1))
