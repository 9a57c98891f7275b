"""The training cells' control at a size a test run holds: the reference
computed in fp8 in the program's place reads several times what the
program (bfloat16) reads against the float32 reference, on three seeds.
The chip's readings at each cell's own size, from which the limits are
set, are in PERF.md; ``perfbench/calibrate.py`` makes them."""
from __future__ import annotations

import importlib.util

import pytest

import perfbench_tiny as tiny

from harness import spec


@pytest.fixture(scope="module")
def calibrate():
    path = tiny.BENCH / "calibrate.py"
    s = importlib.util.spec_from_file_location("perfbench_calibrate", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def _readings(calibrate, cell, capsys, **kw):
    import json

    calibrate._train_readings(cell, [11, 12, 13], **kw)
    return [json.loads(x) for x in capsys.readouterr().out.splitlines()]


def test_fp8_control_reads_above_the_program(calibrate, capsys):
    cell = spec.load_cell("phi3-train-async-full")
    tiny.shrink(cell)
    prog = _readings(calibrate, cell, capsys)
    ctrl = _readings(calibrate, cell, capsys, quant="fp8")
    for p, c in zip(prog, ctrl):
        assert c["grad_gap"] >= 3 * p["grad_gap"], (p, c)
        assert c["loss_gap"] >= 3 * p["loss_gap"], (p, c)
