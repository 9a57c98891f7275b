"""The reduction of the program's stage spans (``harness.stages``), on the
CPU: self time per thread, program spans and idle gaps by program span,
the readings per save and per recovery, and nothing read where the trace
holds no program span."""
from __future__ import annotations

import glob
import threading

import jax
import pytest

import perfbench_tiny as tiny

from harness import readings, stages, tracing

HOST = "/host:CPU"
MAIN, WORKER, POOL = (HOST, 0), (HOST, 1), (HOST, 2)
NS = 1e-9


def _s(line, name, start, end, **stats):
    return stages.HostSpan(line, name, start, end, stats)


def _device(*busy):
    return [tracing.Event("/device:TPU:0", "XLA Ops", f"op.{i}", a, b - a)
            for i, (a, b) in enumerate(busy)]


def _save_trace():
    """One save: the call on the main thread, the pipeline on a worker.
    A worker fsync of an earlier save falls inside the call's interval on
    another thread, and must not count as the call's child."""
    spans = [
        _s(MAIN, "bench.window", 0, 1000),
        _s(MAIN, "bench.step", 0, 400),
        _s(MAIN, "bench.save", 400, 450),
        _s(MAIN, "veloc.checkpoint", 410, 440, ckpt="s:1:0"),
        _s(MAIN, "veloc.interval", 412, 420),
        _s(WORKER, "veloc.fsync", 415, 425),
        _s(MAIN, "bench.step", 450, 1000),
        _s(WORKER, "veloc.pipeline", 460, 960, ckpt="s:1:0"),
        _s(WORKER, "veloc.serialize", 470, 800),
        _s(WORKER, "veloc.d2h", 480, 560, bytes=100),
        _s(WORKER, "veloc.digest", 600, 700, bytes=100),
        _s(WORKER, "veloc.digest", 720, 760, bytes=50),
        _s(WORKER, "veloc.l1-local", 800, 850),
        _s(WORKER, "veloc.l3-flush", 860, 950),
        _s(WORKER, "veloc.l3.pace", 870, 880),
        _s(WORKER, "veloc.fsync", 900, 940),
    ]
    # idle: 0-100 and 300-500 (outside every program span), 540-650
    # (serialize is the innermost open span), 980-1000 (after the save)
    return stages.reduce(spans, _device((100, 300), (500, 540),
                                        (650, 980)))


def test_self_time_program_spans_and_gaps():
    st = _save_trace()
    p = st.program
    assert p["veloc.serialize"] == {"count": 1, "total_s": 330 * NS,
                                    "self_s": pytest.approx(110 * NS),
                                    "bytes": 0}
    assert p["veloc.pipeline"]["self_s"] == pytest.approx(30 * NS)
    assert p["veloc.l3-flush"]["self_s"] == pytest.approx(40 * NS)
    # the other thread's fsync is no child of the call
    assert p["veloc.checkpoint"]["self_s"] == pytest.approx(22 * NS)
    assert p["veloc.digest"]["count"] == 2
    assert p["veloc.digest"]["bytes"] == 150
    assert p["veloc.fsync"]["total_s"] == pytest.approx(50 * NS)
    assert st.program_gaps == {"none": pytest.approx(320 * NS),
                               "veloc.serialize": pytest.approx(110 * NS)}
    assert st.counts == {"bench.save": 1, "bench.recovery": 0}


def test_save_stages_per_save():
    st = _save_trace()
    got = {m: st.metric(m, 2) for m in stages.METRICS
           if m.endswith(".protect")}
    assert got == {"d2h_s.protect": pytest.approx(40 * NS),
                   "serialize_s.protect": pytest.approx(55 * NS),
                   "digest_s.protect": pytest.approx(70 * NS),
                   "l1_put_s.protect": pytest.approx(25 * NS),
                   "l3_flush_s.protect": pytest.approx(45 * NS)}
    # the five stages cover the pipeline but for its own 30 ns
    assert sum(got.values()) * 2 == pytest.approx(470 * NS)
    # idle 460-500 and 540-650 fall while the pipeline is open
    assert st.metric(stages.SAVE_IDLE, 1) == pytest.approx(150 * NS)
    for m in [*stages.METRICS, stages.SAVE_IDLE]:
        assert st.metric(m, 0) is None
    assert st.metric("restore_fetch_s.resume", 1) is None   # no such span


def test_restore_stages_per_recovery():
    spans = [_s(MAIN, "bench.window", 0, 1000)]
    for t0 in (0, 500):
        spans += [
            _s(MAIN, "bench.recovery", t0, t0 + 450),
            _s(MAIN, "veloc.restore", t0 + 10, t0 + 400, restore="s:0"),
            _s(MAIN, "veloc.restore.plan", t0 + 10, t0 + 30),
            _s(MAIN, "veloc.restore.load", t0 + 30, t0 + 250, version=3),
            # the fetch runs on a reader-pool thread inside the load
            _s(POOL, "veloc.tier.get", t0 + 40, t0 + 120, tier="pfs"),
            _s(MAIN, "veloc.digest", t0 + 130, t0 + 150, bytes=64),
            _s(MAIN, "veloc.restore.decode", t0 + 160, t0 + 240),
            _s(MAIN, "veloc.digest", t0 + 170, t0 + 200, bytes=32),
            _s(MAIN, "veloc.restore.place", t0 + 250, t0 + 390),
            _s(MAIN, "veloc.restore.device_put", t0 + 300, t0 + 380,
               bytes=32),
        ]
    spans.append(_s(POOL, "veloc.tier.get", 460, 480, tier="pfs"))
    # idle 15-25 and 515-525 in the plans, 320-360 and 820-860 in the
    # device_puts (inside place: the later-starting span is the innermost)
    st = stages.reduce(spans, _device((0, 15), (25, 320), (360, 515),
                                      (525, 820), (860, 1000)))
    n = st.counts["bench.recovery"]
    assert n == 2
    got = {m: st.metric(m, n) for m in stages.METRICS
           if m.endswith(".resume")}
    # the get between the recoveries lies in no restore: left out
    assert got == {"restore_fetch_s.resume": pytest.approx(80 * NS),
                   "restore_verify_s.resume": pytest.approx(50 * NS),
                   "restore_decode_s.resume": pytest.approx(50 * NS),
                   "restore_assemble_s.resume": pytest.approx(60 * NS),
                   "restore_device_put_s.resume": pytest.approx(80 * NS)}
    plan = st.seconds("veloc.restore.plan", "veloc.restore", False) / n
    assert plan + sum(got.values()) == pytest.approx(340 * NS)
    assert st.metric("d2h_s.protect", 1) is None
    assert st.metric(stages.SAVE_IDLE, 1) is None
    gaps = st.program_gaps
    assert gaps == {"veloc.restore.plan": pytest.approx(20 * NS),
                    "veloc.restore.device_put": pytest.approx(80 * NS)}


def test_recorded_chip_trace_without_program_spans():
    """The benchmark's committed trace holds no program span: nothing is
    read, and the idle time is the one ``tracing.reduce`` finds."""
    path = str(tiny.REPO / "tests" / "perfbench" / "data" /
               "small_trace.xplane.pb")
    events = tracing.load_events(path)
    st = stages.reduce(stages.host_spans(path), events)
    s = tracing.reduce(events)
    assert st.program == {}
    assert st.program_gaps == {
        "none": pytest.approx(s.window_s - s.busy_s, rel=1e-9)}
    out = stages.summary(path)
    assert out["metrics"] == {} and out["program"] == {}
    assert stages.reduce(stages.host_spans(path),
                         [e for e in events
                          if not e.plane.startswith("/device")]) is None


def test_threads_of_one_name_are_told_apart(tmp_path):
    """Every Python thread's line has the same name; the loader keeps
    them apart by position, with each span's stats."""
    def work():
        with jax.profiler.TraceAnnotation("veloc.pipeline", ckpt="s:2:0"):
            with jax.profiler.TraceAnnotation("veloc.digest", bytes=8):
                pass

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.save"):
            with jax.profiler.TraceAnnotation("veloc.checkpoint",
                                              ckpt="s:2:0"):
                t = threading.Thread(target=work)
                t.start()
                t.join(30)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = {s.name: s for s in stages.host_spans(path)}
    assert spans["veloc.checkpoint"].line == spans["bench.save"].line
    assert spans["veloc.pipeline"].line != spans["veloc.checkpoint"].line
    assert spans["veloc.digest"].line == spans["veloc.pipeline"].line
    assert spans["veloc.pipeline"].stats == {"ckpt": "s:2:0"}
    assert spans["veloc.digest"].stats == {"bytes": 8}


def test_recorded_chip_save_and_restore_reduce():
    """A 14 MiB state saved and restored under a trace on a TPU v5e
    (``veloc.*`` spans inside the benchmark's ``bench.*`` ones): every
    stage reading is there, the stages cover their root spans, and the
    program's spans share the device's clock."""
    path = str(tiny.REPO / "tests" / "perfbench" / "data" /
               "tiny_save.xplane.pb")
    events = tracing.load_events(path)
    st = stages.reduce(stages.host_spans(path), events)
    assert st.counts == {"bench.save": 1, "bench.recovery": 1}
    got = {m: st.metric(m, 1) for m in [*stages.METRICS, stages.SAVE_IDLE]}
    assert all(v is not None and v > 0 for v in got.values()), got
    p = st.program
    save = sum(v for m, v in got.items() if m.endswith(".protect"))
    assert 0.9 <= save / p["veloc.pipeline"]["total_s"] <= 1.0
    restore = sum(v for m, v in got.items() if m.endswith(".resume")) + \
        p["veloc.restore.plan"]["self_s"]
    assert 0.9 <= restore / p["veloc.restore"]["total_s"] <= 1.0
    assert p["veloc.digest"]["count"] == 10   # 4 regions + shard, twice
    assert p["veloc.d2h"]["bytes"] == p["veloc.restore.device_put"]["bytes"]

    def one(name):
        found = [s for s in st.spans if s.name == name]
        assert len(found) == 1, name
        return found[0]

    assert one("bench.save").covers(one("veloc.checkpoint"))
    assert one("bench.restore").covers(one("veloc.restore"))
    assert one("bench.window").covers(one("veloc.pipeline"))
    assert one("veloc.pipeline").line != one("veloc.checkpoint").line
    s = tracing.reduce(events)
    assert s.module(readings.CHECKSUM_PROGRAM)[0] == 10
    assert sum(st.program_gaps.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
