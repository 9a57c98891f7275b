"""A whole run of each traffic mix at a size the CPU holds
(``JAX_PLATFORMS=cpu``): set-up, window, saves read back from every level,
the reference's comparison and the result line, which on a CPU carries no
metric.  A cell on four chips runs in a child process on four virtual CPU
devices.  And a reference with its own CPU sizes, a configuration, cells
on one and on four chips, a traffic mix, limit files and a metric reader
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
    cell = spec.load_cell(workload, root)
    if cell.chips > 1:
        [(rc, res, err)] = tiny.run_on_chips(root, workload, cell.chips)
        assert res["device"]["count"] == cell.chips
        assert res["checks"]["state_leaves_off_mesh"]["value"] == 0
    else:
        with tiny.jax_cache_config():
            rc, res, err = tiny.run(root, workload, hook=_tiny)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert res["metrics"] == {}          # a CPU run reports no metric
    assert res["device"]["platform"] == "cpu"
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    if cell.traffic.get("pipeline"):
        key = ("ckpt_regions_differing" if "train" in workload
               else "restored_leaves_differing")
        assert res["checks"][key]["value"] == 0


def test_step_counters_are_summed_over_the_window(root, monkeypatch):
    seen = {}
    real = spec.loop

    def loop(cell):
        mod = real(cell)

        class Kept:
            @staticmethod
            def run(ctx):
                seen["out"] = mod.run(ctx)
                return seen["out"]
        return Kept

    monkeypatch.setattr(spec, "loop", loop)
    with tiny.jax_cache_config():
        rc, res, err = tiny.run(root, "phi3-train-nockpt", hook=_tiny)
    assert rc == 0 and res["correct"] is True, err[-3000:]
    rec = seen["out"].record
    # the step's scalars besides its loss: AdamW's gradient norm
    assert set(rec["step_counters"]) == {"grad_norm"}
    assert 0 < rec["step_counters"]["grad_norm"] < float("inf")


#: a reference of its own: the dense one at other CPU sizes
NARROW_TINY = """

TINY = {"file": {"hidden_size": 32, "num_attention_heads": 2,
                 "num_key_value_heads": 2, "intermediate_size": 64,
                 "vocab_size": 300, "num_hidden_layers": 1},
        "program": {"d_model": 32, "num_heads": 2, "num_kv_heads": 2,
                    "head_dim": 16, "d_ff": 64, "vocab_size": 300,
                    "num_layers": 1, "remat": False}}
"""


def test_cell_traffic_and_metric_added_as_files(tmp_path):
    root = tiny.checkout(tmp_path)
    bench_dir = root / "perfbench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    shutil.copy(bench_dir / "configs" / "phi3-mini-3.8b-1l.json",
                bench_dir / "configs" / "dense-small.json")
    # a reference with its own CPU sizes, and a configuration naming it
    (bench_dir / "reference" / "dense_narrow.py").write_text(
        (bench_dir / "reference" / "dense_lm.py").read_text() + NARROW_TINY)
    narrow = json.loads((bench_dir / "configs" /
                         "phi3-mini-3.8b-1l.json").read_text())
    narrow.update(name="dense-narrow", reference="dense_narrow")
    (bench_dir / "configs" / "dense-narrow.json").write_text(
        json.dumps(narrow))
    mix = json.loads((bench_dir / "traffic" / "nockpt-4k.json").read_text())
    mix["check_steps"] = 2
    (bench_dir / "traffic" / "short-mix.json").write_text(json.dumps(mix))
    # a mix on a four-chip mesh, with saves
    mix4 = json.loads((bench_dir / "traffic" /
                       "async-full-4k.json").read_text())
    mix4.update(layout={"mesh": {"data": 4, "model": 1}, "fsdp": True},
                batch=4)
    (bench_dir / "traffic" / "fsdp-mix.json").write_text(json.dumps(mix4))
    for cell in ("dense-short", "narrow-fsdp4"):
        (bench_dir / "limits" / f"{cell}.json").write_text(json.dumps(
            {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3}))
    (bench_dir / "metrics" / "steps.window.py").write_text(
        "def read(run):\n    return run.get('steps')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name in ("dense-small", "dense-narrow"):
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"perfbench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "dense-short", "config": "dense-small",
         "traffic": "short-mix", "chips": 1, "why": "test"},
        {"name": "narrow-fsdp4", "config": "dense-narrow",
         "traffic": "fsdp-mix", "chips": 4, "why": "test"}]
    bench["per_layer"].append({"name": "steps.window", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "tokens_per_s",
                               "workloads": ["dense-short", "narrow-fsdp4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    with tiny.jax_cache_config():
        rc, res, err = tiny.run(root, "dense-short", trace=1, hook=_tiny)
    assert rc == 0 and res["correct"] is True, err[-3000:]
    cell = spec.load_cell("dense-short", root)
    assert [m["name"] for m in cell.per_layer] == ["steps.window"]
    assert spec.metric_reader(cell, "steps.window")({"steps": 7}) == 7

    cell = spec.load_cell("narrow-fsdp4", root)
    tiny.shrink(cell)                    # the new reference's own sizes
    assert (cell.config["hidden_size"], cell.traffic["batch"]) == (32, 4)
    [(rc, res, err)] = tiny.run_on_chips(root, "narrow-fsdp4", 4)
    assert rc == 0 and res["correct"] is True, err[-3000:]
    assert res["device"]["count"] == 4
    assert res["checks"]["ckpt_regions_differing"]["value"] == 0

    after = {p: p.read_bytes() for p in before}
    assert after == before               # no existing file was edited
