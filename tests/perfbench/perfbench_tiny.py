"""Shared helpers of the benchmark harness's CPU tests: a copy of the
benchmark in a temporary checkout, and the cells shrunk to a size a CPU
holds (every width cut, every structure kept)."""
from __future__ import annotations

import contextlib
import io
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "perfbench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

#: the widths each reference's configuration is cut to, and the program
#: configuration fields that say the same
TINY = {
    "dense_lm": ({"hidden_size": 64, "num_attention_heads": 4,
                  "num_key_value_heads": 4, "intermediate_size": 128,
                  "vocab_size": 500, "num_hidden_layers": 2},
                 {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
                  "head_dim": 16, "d_ff": 128, "vocab_size": 500,
                  "num_layers": 2, "remat": False}),
}
#: limits for the tiny cells computed in float32 (the program then agrees
#: with the reference to ~1e-5)
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3,
               "resume_loss_gap": 0.0}


def workloads() -> list:
    with open(REPO / "BENCHMARK.json") as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def checkout(tmp: Path) -> Path:
    """A checkout holding only BENCHMARK.json and the benchmark's files."""
    root = tmp / "checkout"
    root.mkdir()
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def shrink(cell, *, compute_dtype: str | None = None, seq_len: int = 64,
           batch: int = 2, limits: dict | None = None) -> None:
    c = cell.config
    sizes, fields = TINY[c["reference"]]
    c.update(sizes)
    prog = dict(c["program"].get("fields", {}), **fields)
    if compute_dtype:
        prog["compute_dtype"] = compute_dtype
    c["program"]["fields"] = prog
    cell.traffic["seq_len"] = seq_len
    cell.traffic["batch"] = batch
    if limits is not None:
        cell.limits.update({k: v for k, v in limits.items()
                            if k in cell.limits})


def run(root: Path, workload: str, *, seed: int = 2147483701,
        seconds: float = 1.0, trace: int = 0, hook=None,
        allow_cpu: bool = True) -> tuple[int, dict | None, str]:
    """(exit status, result line or None, standard error) of one run."""
    from harness import cli

    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cli.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, allow_cpu=allow_cpu, cell_hook=hook)
    lines = [x for x in out.getvalue().splitlines() if x.strip()]
    result = json.loads(lines[-1]) if lines else None
    return rc, result, err.getvalue()


@contextlib.contextmanager
def jax_cache_config():
    """The persistent compile cache off while the harness runs in a test
    process, and its settings put back afterwards."""
    import jax

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
