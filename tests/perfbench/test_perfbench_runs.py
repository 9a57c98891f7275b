"""A whole run of each traffic mix at a size the CPU holds
(``JAX_PLATFORMS=cpu``): set-up, window, saves read back from every level,
the reference's comparison and the result line, which on a CPU carries no
metric.  And a cell, a traffic mix, a limit file and a metric reader
added as new files, with no existing file edited."""
from __future__ import annotations

import json
import shutil

import pytest

import perfbench_tiny as tiny

from harness import spec


def _tiny(cell):
    tiny.shrink(cell, compute_dtype="float32", limits=tiny.TINY_LIMITS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", tiny.workloads())
def test_each_traffic_mix_runs_whole_on_cpu(root, workload):
    with tiny.jax_cache_config():
        rc, res, err = tiny.run(root, workload, hook=_tiny)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["metrics"] == {}          # a CPU run reports no metric
    assert res["device"]["platform"] == "cpu"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    if spec.load_cell(workload, root).traffic.get("pipeline"):
        key = ("ckpt_regions_differing" if "train" in workload
               else "restored_leaves_differing")
        assert res["checks"][key]["value"] == 0


def test_cell_traffic_and_metric_added_as_files(tmp_path):
    root = tiny.checkout(tmp_path)
    bench_dir = root / "perfbench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    shutil.copy(bench_dir / "configs" / "phi3-mini-3.8b-1l.json",
                bench_dir / "configs" / "dense-small.json")
    mix = json.loads((bench_dir / "traffic" / "nockpt-4k.json").read_text())
    mix["check_steps"] = 2
    (bench_dir / "traffic" / "short-mix.json").write_text(json.dumps(mix))
    (bench_dir / "limits" / "dense-short.json").write_text(
        json.dumps({"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}))
    (bench_dir / "metrics" / "steps.window.py").write_text(
        "def read(run):\n    return run.get('steps')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dense-small", "source": "test",
                             "file": "perfbench/configs/dense-small.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dense-short", "config": "dense-small",
                               "traffic": "short-mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps.window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "tokens_per_s",
                               "workloads": ["dense-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    with tiny.jax_cache_config():
        rc, res, err = tiny.run(root, "dense-short", trace=1, hook=_tiny)
    assert rc == 0 and res["correct"] is True, err[-3000:]
    cell = spec.load_cell("dense-short", root)
    assert [m["name"] for m in cell.per_layer] == ["steps.window"]
    assert spec.metric_reader(cell, "steps.window")({"steps": 7}) == 7
    after = {p: p.read_bytes() for p in before}
    assert after == before               # no existing file was edited
