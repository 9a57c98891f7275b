"""The on-chip benchmark's harness, on the CPU: BENCHMARK.json against its
contract, every file found by name, the arithmetic over a window, the
trace reduction, the metric readers, and the refusal to measure without a
chip."""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

import perfbench_tiny as tiny

from harness import flops, readings, shardfile, spec, stats, tracing

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(tiny.REPO / "BENCHMARK.json") as f:
        return json.load(f)


def test_benchmark_json_keeps_its_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200
    used = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        used.add(w["config"])
    assert used == set(configs)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"].get("workloads") is None
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        for w in m["workloads"]:   # each cell listed reports the moved one
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)
    for w in cells:   # setup_s, one more end-to-end and one per-layer metric
        assert sum(1 for m in bench["end_to_end"]
                   if w in m.get("workloads", cells)) >= 2
        assert any(w in m["workloads"] for m in bench["per_layer"])
    assert len(json.dumps(bench)) <= 64 * 1024


@pytest.mark.parametrize("workload", tiny.workloads())
def test_every_file_of_a_cell_loads_by_name(workload, tmp_path):
    cell = spec.load_cell(workload, tiny.checkout(tmp_path))
    assert cell.config["name"] == cell.config_name
    ref = spec.reference(cell)
    assert callable(ref.loss) and callable(ref.layout)
    assert set(ref.TINY) == {"file", "program"}   # its own CPU sizes
    assert callable(spec.loop(cell).run)
    assert cell.limits
    for m in cell.per_layer:
        assert callable(spec.metric_reader(cell, m["name"]))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2


def test_reference_follows_the_configuration_file():
    from harness import program, weights

    cell = spec.load_cell("phi3-train-nockpt")
    tiny.shrink(cell)
    c, ref = cell.config, spec.reference(cell)
    assert (c["rms_norm_eps"], c["sliding_window"]) == (1e-6, None)
    program.model_config(cell, ref)   # the program runs what the file says
    params = weights.params_maker(ref.layout(c))(weights.seed_key(5))
    tokens = np.arange(2 * 16).reshape(2, 16) % c["vocab_size"]
    base = float(ref.loss(params, tokens, c))
    assert float(ref.loss(params, tokens, dict(c, rms_norm_eps=0.5))) != base
    assert float(ref.loss(params, tokens, dict(c, rope_theta=50.0))) != base
    with pytest.raises(ValueError):
        ref.dims(dict(c, sliding_window=2047))
    cell.config = dict(c, rope_theta=50.0)
    with pytest.raises(ValueError):
        program.model_config(cell, ref)


def test_percentile_and_rate_over_the_whole_window():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert sum(1 for x in range(200) if x > stats.percentile(
        range(200), 95)) == 10        # 200 steps leave ten beyond p95
    assert stats.rate(400 * 4096, 50.0) == pytest.approx(32768.0)
    assert stats.mean([1.0, 2.0, 6.0]) == 3.0
    with pytest.raises(ValueError):
        stats.rate(10, 0)


def test_model_counts():
    shapes = {"['emb']": (10, 4), "['blocks'][0]['w']": (4, 4),
              "['lm_head']": (4, 10)}
    assert flops.param_counts(shapes) == {"total": 96, "embed": 80,
                                          "non_embed": 16}
    assert flops.model_flops(shapes, 3) == 6 * 16 * 3


def test_model_flops_counts_the_share_a_token_uses():
    shapes = {"['emb']": (10, 4), "['blocks'][0]['w']": (4, 4),
              "['blocks'][0]['experts']": (8, 4, 4), "['lm_head']": (4, 10)}
    dense = flops.model_flops(shapes, 3)
    assert dense == 6.0 * (16 + 128) * 3
    # every leaf wholly active reads as the dense count, bit for bit
    assert flops.model_flops(shapes, 3, {}) == dense
    assert flops.model_flops(shapes, 3, {p: 1 for p in shapes}) == dense
    # a token uses 2 of the 8 experts
    top2 = flops.model_flops(shapes, 3, {"['blocks'][0]['experts']": 0.25})
    assert top2 == 6.0 * (16 + 32) * 3


def test_mfu_divides_by_the_cells_chips():
    read = spec.metric_reader(spec.load_cell("phi3-train-async-full"),
                              "mfu.train")
    run = {"on_chip": True, "device_kind": "TPU v5 lite",
           "tokens_per_s": 30000.0, "flops_per_token": 6 * 113e6}
    one = read(dict(run, chips=1))
    assert one == read(run) == 100.0 * 6 * 113e6 * 3e4 / 197e12
    assert read(dict(run, chips=4)) == pytest.approx(one / 4)


def test_reference_by_rows_is_the_mean_of_the_batch():
    import jax
    import jax.numpy as jnp

    from harness import refcheck, weights

    cell = spec.load_cell("phi3-train-nockpt")
    tiny.shrink(cell)
    c, ref, h = cell.config, spec.reference(cell), cell.traffic["optimizer"]
    make = weights.params_maker(ref.layout(c))
    key = weights.seed_key(7)
    tokens = np.arange(3 * 16).reshape(3, 16) * 7 % c["vocab_size"]
    got = refcheck.run_reference(ref, c, h, make, key, [tokens])
    with jax.default_matmul_precision("highest"):
        params = make(key)
        loss, grads = jax.value_and_grad(ref.loss)(params, jnp.asarray(
            tokens), c)
        zeros = jax.tree.map(jnp.zeros_like, params)
        new, _, _, g = refcheck.adamw(params, zeros, zeros, grads, 1.0, h)
    assert got["losses"][0] == pytest.approx(float(loss), rel=1e-6)
    np.testing.assert_allclose(got["grad_norms"],
                               np.asarray(refcheck.leaf_norms(g)), rtol=1e-5)
    np.testing.assert_allclose(
        got["change_norms"], np.asarray(refcheck.change_norms(new, params)),
        rtol=1e-4)


def test_layout_mesh_is_the_cells_chips():
    from harness import program

    assert program.mesh({}, 1) is None          # no layout: one device
    with pytest.raises(ValueError):
        program.mesh({"layout": {"mesh": {"data": 4, "model": 1}}}, 1)


def _events():
    E = tracing.Event
    host, dev = "/host:CPU", "/device:TPU:0"
    return [
        E(host, "python", "bench.window", 0, 1000),
        E(host, "python", "bench.step", 0, 400),
        E(host, "python", "bench.save", 400, 300),
        E(dev, "XLA Ops", "fusion.1", 100, 200),
        E(dev, "XLA Ops", "fusion.2", 250, 100),
        E(dev, "XLA Ops", "custom-call.3", 500, 100),
        E(dev, "XLA Modules", "jit_train_step(12)", 100, 250),
        E(dev, "XLA Modules", "jit__checksum_j(7)", 500, 100),
    ]


def test_trace_reduction_idle_share_programs_and_gaps():
    s = tracing.reduce(_events())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(350e-9)        # union, not the sum
    assert s.modules == {"jit_train_step": (1, pytest.approx(250e-9)),
                         "jit__checksum_j": (1, pytest.approx(100e-9))}
    assert s.idle_gaps == {"bench.step": pytest.approx(100e-9),
                           "bench.save": pytest.approx(150e-9),
                           "none": pytest.approx(400e-9)}
    assert readings.idle_share({"trace": s}) == pytest.approx(65.0)
    b = tracing.breakdown(s)
    assert b["device_ops"][0][0] == "jit_train_step"
    assert b["idle_gaps"][0] == ["none", pytest.approx(400e-9)]
    assert tracing.reduce([e for e in _events()
                           if not e.plane.startswith("/device")]) is None


def test_checksum_time_counts_every_digest_or_none():
    s = tracing.reduce(_events())
    run = {"on_chip": True, "device_kind": "TPU v5 lite", "trace": s,
           "region_bytes": [1 << 20]}
    # one save = two digests, but the trace holds one: nothing to read
    assert readings.checksum_ms(run, 1, 2) is None
    s.modules["jit__checksum_j"] = (4, 3e-3)
    assert readings.checksum_ms(run, 2, 2) == pytest.approx(1.5)
    assert readings.checksum_ms(dict(run, on_chip=False), 2, 2) is None
    reader = spec.metric_reader(spec.load_cell("phi3-train-async-full"),
                                "checksum_ms.protect")
    assert reader(dict(run, saves=[{}, {}])) == pytest.approx(1.5)


def test_metric_readers_read_the_record():
    cell = spec.load_cell("phi3-train-async-full")
    run = {"on_chip": True, "device_kind": "TPU v5 lite", "trace": None,
           "tokens_per_s": 30000.0, "flops_per_token": 6 * 113e6,
           "saves": [{"app_blocking_s": 0.002, "shard_bytes": 3.7e9,
                      "protect_s": 34.0}], "region_bytes": [1]}
    got = {m["name"]: spec.metric_reader(cell, m["name"])(run)
           for m in cell.per_layer}
    assert got["mfu.train"] == pytest.approx(100 * 6 * 113e6 * 3e4 / 197e12)
    assert got["save_block_ms"] == pytest.approx(2.0)
    assert got["shard_mb.protect"] == pytest.approx(3700.0)
    assert got["device_idle.train"] is None      # no trace: nothing read
    assert got["checksum_ms.protect"] is None
    off = dict(run, on_chip=False)
    assert spec.metric_reader(cell, "mfu.train")(off) is None


def test_recorded_chip_trace_reduces():
    """A trace recorded on a TPU v5e: one matmul program under a
    ``bench.step`` span, one 64 MiB digest under ``bench.save``."""
    path = tiny.REPO / "tests" / "perfbench" / "data" / \
        "small_trace.xplane.pb"
    s = tracing.reduce(tracing.load_events(str(path)))
    assert s is not None and s.devices == 1
    assert 0 < s.busy_s < s.window_s
    n, sec = s.module(readings.CHECKSUM_PROGRAM)
    assert n == 1 and 0 < sec < s.busy_s
    assert set(s.idle_gaps) <= {"bench.step", "bench.save", "none"}


def test_shard_reader_finds_a_changed_byte():
    from repro.core import format as fmt

    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    b = np.arange(5, dtype=np.int32)
    blob = fmt.serialize_shard([fmt.Region("a", a), fmt.Region("b", b)], {})
    assert shardfile.mismatches(blob, [a, b]) == []
    bad = bytearray(blob)
    bad[-1] ^= 1
    assert shardfile.mismatches(bytes(bad), [a, b]) == ["b"]
    assert shardfile.mismatches(blob, [a]) == ["2 regions for 1 leaves"]
    assert shardfile.mismatches(b"junk" * 4, [a])[0].startswith("unreadable")


def _sharded(a, b, drop=None):
    """A shard holding ``a`` as four row blocks and ``b`` whole, as a save
    of one process's four devices writes them."""
    from repro.core import format as fmt

    regions = [fmt.Region(f"['a']@{i},0", a[i:i + 2], global_shape=a.shape)
               for i in range(0, 8, 2) if i != drop]
    return fmt.serialize_shard(regions + [fmt.Region("['b']", b)], {})


def test_shard_reader_compares_each_shard_with_its_slice():
    a = np.arange(32, dtype=np.float32).reshape(8, 4)
    b = np.arange(5, dtype=np.int32)
    blob = _sharded(a, b)
    assert shardfile.mismatches(blob, [a, b]) == []
    # a changed byte in one shard's region names that shard
    start = next(r["start"] for r in shardfile.regions(blob)
                 if r["name"] == "['a']@4,0")
    bad = bytearray(blob)
    bad[start + 3] ^= 1
    assert shardfile.mismatches(bytes(bad), [a, b]) == ["['a']@4,0"]
    # the shard compared with another slice of its leaf differs
    c = a.copy()
    c[6:] += 1
    assert shardfile.mismatches(blob, [c, b]) == ["['a']@6,0"]
    # a missing shard, and a surplus leaf
    assert shardfile.mismatches(_sharded(a, b, drop=2), [a, b]) == [
        "['a']: shards cover 24 of 32 elements"]
    assert shardfile.mismatches(blob, [a]) == ["2 regions for 1 leaves"]
    # a shard whose header names another global shape
    assert shardfile.mismatches(blob, [a[:6], b]) == [
        f"['a']@{i},0" for i in range(0, 8, 2)] + [
        "['a']: shards cover 0 of 24 elements"]


def test_no_chip_no_result(tmp_path):
    root = tiny.checkout(tmp_path)
    with tiny.jax_cache_config():
        rc, result, err = tiny.run(root, "phi3-train-nockpt",
                                   allow_cpu=False)
    assert rc != 0 and result is None
    assert "no TPU" in err
