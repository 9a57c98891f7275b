"""``run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``.

Finds the cell's files, refuses a host without the chips the cell asks
for, runs the loop its traffic names, and prints one JSON line: the
cell's end-to-end metrics (``--trace 0``) or its per-layer metrics
(``--trace 1``), the device, and last the numbers compared for
``correct``, each beside its limit.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Callable, Optional

from harness import spec


def parse(argv):
    ap = argparse.ArgumentParser(prog="perfbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else one fixed directory inside the checkout; every program is kept,
    however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _finite(v):
    """A number JSON can hold; a NaN or infinity is reported as null."""
    return v if math.isfinite(v) else None


def main(argv=None, *, root: Path = spec.ROOT,
         t_start: Optional[float] = None, allow_cpu: bool = False,
         cell_hook: Optional[Callable] = None) -> int:
    """Exit status; the result line goes to stdout.  ``allow_cpu`` and
    ``cell_hook`` (which may resize the cell) exist for the harness's own
    CPU tests: on a CPU the run reports no metric."""
    t_start = time.monotonic() if t_start is None else t_start
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    if cell_hook is not None:
        cell_hook(cell)

    import jax

    from harness.context import CompileCounter, Context
    from harness import peaks

    use_compile_cache(root)
    devices = jax.devices()
    platform = devices[0].platform
    on_chip = platform == "tpu"
    if not on_chip and not allow_cpu:
        print(f"[perfbench] no TPU: JAX found {platform!r} devices; "
              f"nothing measured", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"[perfbench] {cell.name} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 3
    if on_chip:
        peaks.lookup(devices[0].device_kind)   # unknown chip: an error
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), t_start=t_start, on_chip=on_chip,
                  device_kind=devices[0].device_kind,
                  work_dir=root / ".perfbench" / cell.name,
                  compiles=CompileCounter.counter())
    ctx.fresh_dirs()
    try:
        out = spec.loop(cell).run(ctx)
    finally:
        shutil.rmtree(ctx.work_dir, ignore_errors=True)
    dev = {"platform": platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}

    metrics = {}
    if on_chip:
        if args.trace:
            run = dict(out.record, on_chip=True, trace=out.trace,
                       device_kind=ctx.device_kind, chips=cell.chips)
            for m in cell.per_layer:
                v = spec.metric_reader(cell, m["name"])(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in cell.end_to_end:
                if m["name"] in out.e2e:
                    metrics[m["name"]] = {"value": out.e2e[m["name"]],
                                          "unit": m["unit"]}
    if args.trace and out.trace is not None:
        dev["busy_s"] = out.trace.busy_s
        dev["window_s"] = out.trace.window_s

    result = {"correct": out.correct, "attempted": out.attempted,
              "failed": out.failed, "metrics": metrics, "device": dev,
              "window_compiles": out.window_compiles}
    if args.trace and out.trace is not None:
        from harness.tracing import breakdown
        result["breakdown"] = breakdown(out.trace)
    result["checks"] = {k: {"value": _finite(v), "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    for k, (v, lim) in out.checks.items():
        ok = "ok" if v <= lim else "FAIL"
        print(f"[perfbench] check {k} = {v!r} (limit {lim!r}) {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
