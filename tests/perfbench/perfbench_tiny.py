"""Shared helpers of the benchmark harness's CPU tests: a copy of the
benchmark in a temporary checkout, the cells shrunk to the sizes their
reference names for a CPU (every width cut, every structure kept), and a
run of a cell on several chips in a process of its own, on as many virtual
CPU devices."""
from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "perfbench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

#: limits for the tiny cells computed in float32 (the program then agrees
#: with the reference to ~1e-5)
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-3,
               "resume_loss_gap": 0.0}


def workloads(chips: int | None = None) -> list:
    """The benchmark's cells, or those that ask for ``chips`` chips."""
    with open(REPO / "BENCHMARK.json") as f:
        return [w["name"] for w in json.load(f)["workloads"]
                if chips is None or w["chips"] == chips]


def float32(cell) -> None:
    """The cell at its tiny size, computed in float32, under the tiny
    limits."""
    shrink(cell, compute_dtype="float32", limits=TINY_LIMITS)


def checkout(tmp: Path) -> Path:
    """A checkout holding only BENCHMARK.json and the benchmark's files."""
    root = tmp / "checkout"
    root.mkdir()
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def shrink(cell, *, compute_dtype: str | None = None, seq_len: int = 64,
           batch: int | None = None, limits: dict | None = None) -> None:
    """Cut ``cell`` to the sizes its reference names (``TINY``), in place.
    ``batch`` defaults to 2 rows, or one row a chip on a layout's mesh."""
    from harness import spec

    c = cell.config
    tiny = spec.reference(cell).TINY
    c.update(tiny["file"])
    prog = dict(c["program"].get("fields", {}), **tiny["program"])
    if compute_dtype:
        prog["compute_dtype"] = compute_dtype
    c["program"]["fields"] = prog
    if batch is None:
        lay = cell.traffic.get("layout")
        batch = lay["mesh"].get("data", 1) if lay else 2
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


def run_on_chips(root: Path, workload: str, chips: int,
                 faults=(None,), children: int = 1,
                 timeout: float = 300.0) -> list:
    """``run`` of ``workload`` cut by ``float32``, once for each fault
    planted (None: none), in ``children`` child processes side by side,
    each of which sees ``chips`` virtual CPU devices; this process keeps
    its one device.  The control (``fp8_control``) runs at the cell's
    stated precision and committed limits instead.  A list of (exit
    status, result line or None, end of standard error), in the order of
    ``faults``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={chips}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO / "src"), env.get("PYTHONPATH")) if p)
    names = [f or "-" for f in faults]
    parts = [names[i::children] for i in range(children) if names[i::children]]
    procs = [subprocess.Popen([sys.executable, __file__, str(root), workload,
                               *part], env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for part in parts]
    outs = []
    try:
        for proc in procs:
            out, err = proc.communicate(timeout=timeout)
            if proc.returncode != 0:
                raise RuntimeError(f"child exited {proc.returncode}: "
                                   f"{err[-3000:]}")
            outs.append([json.loads(x) for x in out.splitlines()
                         if x.startswith("{")])
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    runs = {}
    for part, got in zip(parts, outs):
        runs.update(zip(part, got))
    return [(runs[n]["rc"], runs[n]["result"], runs[n]["err"])
            for n in names]


def _child(root: str, workload: str, *faults) -> None:
    from harness import faults as planted

    for name in faults:
        hook = shrink if name == "fp8_control" else float32
        with jax_cache_config(), (contextlib.nullcontext() if name == "-"
                                  else planted.FAULTS[name]()):
            rc, res, err = run(Path(root), workload, hook=hook)
        print(json.dumps({"rc": rc, "result": res, "err": err[-3000:]}),
              flush=True)


if __name__ == "__main__":
    _child(*sys.argv[1:])
