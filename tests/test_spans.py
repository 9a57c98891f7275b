"""Stage spans of the save pipeline and the restore path
(``repro.core.spans``), read back from a profiler trace taken on the CPU:
each span with its ids, on the thread that did the work, nested under its
request's root span, on the same clock as the caller's own spans."""
from __future__ import annotations

import glob
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import (Cluster, ModuleSpec, PipelineSpec, TierTopology,
                        VelocClient)
from repro.core import format as fmt
from repro.core.capture import host_state_bytes, iter_host_regions
from repro.core.spans import PREFIX, span
from repro.kernels import ops as kops

STREAM = "spans"
SAVE_MODULES = ("interval", "serialize", "l1-local", "l3-flush")


@dataclass
class Span:
    line: tuple      # (plane, index of the line in its plane): one thread
    name: str
    start: int
    end: int
    stats: dict

    def inside(self, other: "Span") -> bool:
        return other.start <= self.start and self.end <= other.end


def _spans(path: str) -> list[Span]:
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((PREFIX, "bench.")):
                    out.append(Span((plane.name, i), e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def _state():
    return {"w": jnp.arange(3000, dtype=jnp.float32).reshape(30, 100),
            "opt": {"m": jnp.ones((8, 16), jnp.bfloat16),
                    "step": jnp.asarray(7, jnp.int32)}}


def _client(scratch, mode="async"):
    spec = PipelineSpec(name=STREAM, mode=mode, modules=[
        ModuleSpec("interval"), ModuleSpec("serialize"), ModuleSpec("local"),
        ModuleSpec("flush")])
    return VelocClient(spec, Cluster(TierTopology(scratch=str(scratch))))


def _save_and_restore(client, state):
    with TraceAnnotation("bench.save"):
        fut = client.checkpoint(state, version=1)
    fut.result(60)
    with TraceAnnotation("bench.restore"):
        version, restored = client.restart_latest(state)
    assert version == 1
    return fut, restored


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One async save and one restore under a profiler trace."""
    root = tmp_path_factory.mktemp("spans")
    client = _client(root / "ckpt")
    state = _state()
    jax.profiler.start_trace(str(root / "trace"))
    try:
        fut, restored = _save_and_restore(client, state)
    finally:
        jax.profiler.stop_trace()
        client.shutdown()
    path, = glob.glob(str(root / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    return {"spans": _spans(path), "fut": fut, "state": state,
            "restored": restored}


def _one(spans, name):
    found = [s for s in spans if s.name == name]
    assert len(found) == 1, (name, found)
    return found[0]


def test_every_span_appears_with_its_ids(traced):
    spans, state = traced["spans"], traced["state"]
    names = {s.name for s in spans}
    for stage in ("checkpoint", "pipeline", *SAVE_MODULES, "d2h", "digest",
                  "l3.pace", "fsync", "restore", "restore.plan",
                  "restore.load", "tier.get", "restore.decode",
                  "restore.place", "restore.device_put"):
        assert PREFIX + stage in names, stage
    ckpt = f"{STREAM}:1:0"
    assert _one(spans, "veloc.checkpoint").stats == {"ckpt": ckpt}
    assert _one(spans, "veloc.pipeline").stats == {"ckpt": ckpt}
    assert _one(spans, "veloc.restore").stats == {"restore": f"{STREAM}:0"}
    assert _one(spans, "veloc.restore.load").stats == {"version": 1}
    assert _one(spans, "veloc.d2h").stats == {
        "bytes": host_state_bytes(state)}
    leaves = jax.tree.leaves(state)
    assert sorted(s.stats["bytes"] for s in spans
                  if s.name == "veloc.restore.device_put") == \
        sorted(x.nbytes for x in leaves)
    assert all(s.stats["bytes"] > 0 for s in spans
               if s.name == "veloc.digest")
    assert all(s.stats.get("tier") for s in spans
               if s.name == "veloc.tier.get")
    assert sum(s.name == "veloc.restore.decode" for s in spans) == \
        len(leaves)


def test_save_stages_nest_under_the_pipeline_on_the_worker(traced):
    spans = traced["spans"]
    save = _one(spans, "bench.save")
    ckpt = _one(spans, "veloc.checkpoint")
    pipe = _one(spans, "veloc.pipeline")
    # the caller's spans share one clock and one thread
    assert ckpt.line == save.line and ckpt.inside(save)
    # the background work runs on another thread, after the call returned
    assert pipe.line != ckpt.line and pipe.start >= ckpt.start
    worker = [s for s in spans if s.name in (
        "veloc.serialize", "veloc.l1-local", "veloc.l3-flush", "veloc.d2h",
        "veloc.l3.pace")]
    assert len(worker) == 5
    for s in worker:
        assert s.line == pipe.line and s.inside(pipe), s
    d2h = _one(spans, "veloc.d2h")
    assert d2h.inside(_one(spans, "veloc.serialize"))
    # every region's digest and the shard's, under serialize
    digests = [s for s in spans if s.name == "veloc.digest"
               and s.inside(pipe)]
    assert len(digests) == len(jax.tree.leaves(traced["state"])) + 1
    assert all(s.line == pipe.line for s in digests)
    # the blocking part (the interval module here) stays on the caller
    assert _one(spans, "veloc.interval").inside(ckpt)


def test_restore_stages_nest_under_the_restore(traced):
    spans = traced["spans"]
    root = _one(spans, "veloc.restore")
    assert root.inside(_one(spans, "bench.restore"))
    stages = [s for s in spans if s.name.startswith("veloc.restore.")
              or (s.start >= root.start and s.name in (
                  "veloc.tier.get", "veloc.digest"))]
    assert stages and all(s.inside(root) and s.line == root.line
                          for s in stages)
    place = _one(spans, "veloc.restore.place")
    for s in spans:
        if s.name == "veloc.restore.device_put":
            assert s.inside(place)
        if s.name == "veloc.restore.decode":
            assert s.inside(_one(spans, "veloc.restore.load"))
    restored = traced["restored"]
    for a, b in zip(jax.tree.leaves(restored),
                    jax.tree.leaves(traced["state"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sync_pipeline_runs_under_the_call(tmp_path):
    client = _client(tmp_path / "ckpt", mode="sync")
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        _save_and_restore(client, _state())
    finally:
        jax.profiler.stop_trace()
        client.shutdown()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    spans = _spans(path)
    ckpt = _one(spans, "veloc.checkpoint")
    pipe = _one(spans, "veloc.pipeline")
    assert pipe.line == ckpt.line and pipe.inside(ckpt)
    assert _one(spans, "veloc.d2h").inside(ckpt)
    for name in SAVE_MODULES:
        assert _one(spans, PREFIX + name).inside(pipe)


def test_an_untraced_save_is_unchanged(traced, tmp_path):
    """Without a trace the spans record nothing: the same results and the
    same shard bytes as a direct serialization of the state."""
    state = traced["state"]
    client = _client(tmp_path / "ckpt")
    try:
        fut = client.checkpoint(state, version=1)
        res = fut.result(60)
        blob = client.cluster.fetch_shard(STREAM, 1, 0)
    finally:
        client.shutdown()
    want = fmt.serialize_shard(list(iter_host_regions(state)), {})
    assert blob == want and res["shard_bytes"] == len(want)
    assert set(res) == set(traced["fut"].results)
    assert traced["fut"].results["shard_bytes"] == len(want)


def test_digest_span_counts_the_bytes_it_copied(tmp_path):
    """``veloc.digest`` carries the buffer's bytes and the bytes the digest
    copied on the host: the tail after its whole 512 KiB tiles."""
    n = 2 * 512 * 1024 + 7
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        kops.digest(np.zeros(n, np.uint8).tobytes())
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    assert _one(_spans(path), "veloc.digest").stats == {"bytes": n,
                                                        "copied": 7}


def test_span_is_a_trace_annotation_with_the_prefix():
    s = span("digest", bytes=3)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:   # no trace running: a no-op
        pass
