"""Readings the correctness limits are set from, made on the chip at the
cell's own size, in one process per workload.

    python3 perfbench/calibrate.py --workload <train cell> \
        --seeds 1,2,... [--control-seeds ...] [--fault half_batch:5,6,7]
    python3 perfbench/calibrate.py --workload <resume cell> \
        --control-seeds ... [--fault restored_element_altered:5,6,7]
    python3 perfbench/calibrate.py --workload <any cell> \
        --runs fp8_control:5,6,7 [--seconds 10]

A training cell's program readings come from its first steps through the
window's own jitted step and feed (what a run's set-up does), compared with
the reference; its control is the reference computed in fp8 in the
program's place; a fault runs the program with the fault planted.  A
resume cell's control and faults run whole short runs of the cell with the
restore's result rounded or altered.  ``--runs`` makes whole short runs of
any cell with a fault or the control planted, each printing its result
line, ``correct`` and the numbers compared beside the cell's committed
limits.  Each reading is one JSON line on standard output.  The
benchmark's own runs never run this.
"""
import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from harness import cli, faults, program, refcheck, rows, spec  # noqa: E402
from harness import weights  # noqa: E402


def _train_readings(cell, seeds, fault=None, quant=None, refs=None):
    """One JSON line per seed: the program (or, with ``fault``, the program
    with the fault planted, or with ``quant``, the reference in that
    precision) against the reference.  The program runs on the traffic's
    layout, as the benchmark's loop runs it; ``refs`` keeps the
    reference's readings of each seed for the next call."""
    ref = spec.reference(cell)
    mc = program.model_config(cell, ref)
    c, tr = cell.config, cell.traffic
    layout = ref.layout(c)
    shape = (tr["batch"], tr["seq_len"], mc.vocab_size)
    h = tr["optimizer"]
    names = list(weights.shapes(layout))
    refs = {} if refs is None else refs
    mesh = program.mesh(tr, cell.chips)
    with program.use_mesh(mesh):
        sh = program.state_shardings(mc, mesh, weights.state_maker(layout))
        feed = program.feeder(mesh)
        step_fn = None
        if seeds and quant is None:
            # the fault is planted while the step is built
            with faults.FAULTS[fault]() if fault else \
                    contextlib.nullcontext():
                step_fn = program.train_step(mc, tr, sh)
        for seed in seeds:
            key = weights.seed_key(seed)
            batches = [rows.tokens(seed, s, *shape)
                       for s in range(tr["check_steps"])]
            t0 = time.monotonic()
            if step_fn is not None:
                fed = iter(batches)

                def one(state):
                    out = step_fn(state, feed(next(fed)))
                    snap = out[1] if len(out) == 3 else None
                    return out[0], snap, float(out[-1]["loss"])

                state = weights.state_maker(layout, sh)(key)
                state, snap, prog = refcheck.program_readings(
                    one, state, tr["check_steps"], h["b1"],
                    lambda: weights.params_maker(
                        layout, sh["params"] if sh is not None else None)(key))
                del state, snap
            else:
                prog = refcheck.run_reference(
                    ref, c, h, weights.params_maker(layout), key, batches,
                    quant=quant)
            if seed not in refs:
                refs[seed] = refcheck.run_reference(
                    ref, c, h, weights.params_maker(layout), key, batches)
            got = refs[seed]
            g = refcheck.gaps(prog, got)
            g.update(_worst(prog, got, names))
            g.update({"seed": seed, "kind": fault or quant or "program",
                      "losses": prog["losses"], "ref_losses": got["losses"],
                      "seconds": time.monotonic() - t0})
            print(json.dumps(g), flush=True)


def _worst(prog, got, names):
    """The worst leaf of each norm gap."""
    out = {}
    for k in ("grad_norms", "change_norms"):
        rel = refcheck.leaf_gaps(prog[k], got[k])
        i = int(rel.argmax())
        out[f"worst_{k}"] = [names[i], float(rel[i]), float(prog[k][i]),
                             float(got[k][i])]
    return out


def _whole_runs(workload, seeds, seconds, fault):
    for seed in seeds:
        with faults.FAULTS[fault]():
            cli.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", action="append", default=[],
                    help="name:seed,seed,...")
    ap.add_argument("--runs", action="append", default=[],
                    help="name:seed,seed,... whole runs with it planted")
    ap.add_argument("--seconds", type=float, default=5.0,
                    help="window of the whole runs")
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = spec.load_cell(args.workload)
    cli.use_compile_cache(spec.ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 3
    if len(jax.devices()) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} chips",
              file=sys.stderr)
        return 3
    if cell.traffic["loop"] == "train":
        refs = {}
        _train_readings(cell, seeds(args.seeds), refs=refs)
        _train_readings(cell, seeds(args.control_seeds), quant="fp8",
                        refs=refs)
        for f in args.fault:
            name, s = f.split(":")
            _train_readings(cell, seeds(s), fault=name, refs=refs)
    else:
        _whole_runs(args.workload, seeds(args.control_seeds), args.seconds,
                    "restored_bf16")
        for f in args.fault:
            name, s = f.split(":")
            _whole_runs(args.workload, seeds(s), args.seconds, name)
    for f in args.runs:
        name, s = f.split(":")
        _whole_runs(args.workload, seeds(s), args.seconds, name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
