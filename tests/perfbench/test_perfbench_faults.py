"""Each fault a cell can have, planted under a whole run at a size the CPU
holds, turns ``correct`` false: a step that returns its state unchanged,
half of the batch left out of the loss, a stored checkpoint with a byte
changed where it is written, a restore that hands back an altered state,
and a restore in bfloat16 (the resume cell's lower-precision control).
A cell on one chip has no exchange between chips to leave out; a cell on
four chips also runs with the gradients' exchange left out, all its runs
in one child process on four virtual CPU devices.  And the training
cells' control, the reference in fp8 in the step's place, fails the
cell's committed limits."""
from __future__ import annotations

import pytest

import perfbench_tiny as tiny

from harness import faults, spec


def _tiny(cell):
    tiny.shrink(cell, compute_dtype="float32", limits=tiny.TINY_LIMITS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload,fault", [
    ("phi3-train-async-full", "state_unchanged"),
    ("phi3-train-async-full", "half_batch"),
    ("phi3-train-async-full", "stored_byte_flipped"),
    ("phi3-train-nockpt", "state_unchanged"),
    ("phi3-train-nockpt", "half_batch"),
    ("phi3-resume-restart", "restored_element_altered"),
    ("phi3-resume-restart", "restored_bf16"),
    ("phi3-resume-restart", "stored_byte_flipped"),
])
def test_planted_fault_is_not_correct(root, workload, fault):
    with tiny.jax_cache_config(), faults.FAULTS[fault]():
        rc, res, err = tiny.run(root, workload, hook=_tiny)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]
    failing = [k for k, c in res["checks"].items()
               if c["value"] is None or c["value"] > c["limit"]]
    assert failing


@pytest.mark.parametrize("workload", ["phi3-train-async-full",
                                      "phi3-train-nockpt"])
def test_fp8_control_fails_the_committed_limits(root, workload):
    committed = spec.load_cell(workload, root).limits
    seen = {}

    def hook(cell):
        tiny.shrink(cell)
        seen["limits"] = dict(cell.limits)

    with tiny.jax_cache_config(), faults.fp8_control():
        rc, res, err = tiny.run(root, workload, hook=hook)
    assert rc == 0, err[-3000:]
    assert seen["limits"] == committed
    assert res["correct"] is False, res["checks"]
    assert any(res["checks"][k]["value"] > lim
               for k, lim in committed.items()), res["checks"]


#: the faults a training cell on several chips can have, and its control
ACROSS_CHIPS = ("state_unchanged", "half_batch", "exchange_left_out",
                "stored_byte_flipped", "fp8_control")


@pytest.fixture(scope="module")
def across_chips(root):
    """{(workload, fault): (exit status, result line, standard error)}."""
    out = {}
    for w in tiny.workloads(chips=4):
        cell = spec.load_cell(w, root)
        runs = tiny.run_on_chips(root, w, cell.chips, faults=ACROSS_CHIPS,
                                 children=2)
        out.update({(w, f): r for f, r in zip(ACROSS_CHIPS, runs)})
    return out


@pytest.mark.parametrize("fault", ACROSS_CHIPS)
@pytest.mark.parametrize("workload", tiny.workloads(chips=4))
def test_planted_fault_across_chips_is_not_correct(root, across_chips,
                                                   workload, fault):
    rc, res, err = across_chips[(workload, fault)]
    assert rc == 0, err[-3000:]
    assert res["device"]["count"] == 4
    assert res["correct"] is False, res["checks"]
    committed = spec.load_cell(workload, root).limits
    failing = [k for k, c in res["checks"].items()
               if c["value"] is None or c["value"] > c["limit"]]
    assert failing
    if fault == "fp8_control":
        assert all(res["checks"][k]["limit"] == lim
                   for k, lim in committed.items())
        assert set(failing) & set(committed), res["checks"]
