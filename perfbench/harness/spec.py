"""Finds a cell's files by name: BENCHMARK.json, the configuration, the
traffic mix, the cell's limits, the metric readers, the plain reference and
the loop the traffic names.  Nothing here knows a cell by name."""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

#: the benchmark's own directory and the checkout it sits in
BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    """Everything one run of one workload reads."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: list  # BENCHMARK.json entries this cell reports (trace 0)
    per_layer: list   # ... and with --trace 1
    root: Path
    bench_dir: Path


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file by path (names may hold '.' and '-')."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    key = f"perfbench_{path.parent.name}_{name}".replace(".", "_") \
        .replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_json(root / "BENCHMARK.json")
    bench_dir = root / bench["paths"][0]
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                limits=limits, end_to_end=e2e, per_layer=per_layer,
                root=root, bench_dir=bench_dir)


def metric_reader(cell: Cell, metric: str):
    """``read(run) -> float | None`` of ``metrics/<metric>.py``."""
    return load_module(cell.bench_dir / "metrics" / f"{metric}.py",
                       metric).read


def reference(cell: Cell):
    """The plain reference module the configuration names."""
    ref = cell.config["reference"]
    return load_module(cell.bench_dir / "reference" / f"{ref}.py", ref)


def loop(cell: Cell):
    """The loop module the traffic names (``loops/<name>.py``)."""
    name = cell.traffic["loop"]
    return load_module(cell.bench_dir / "loops" / f"{name}.py", name)
