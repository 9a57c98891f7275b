"""step_p95_ms.train: 95th percentile of the wall time of every step of the
window (dispatch to loss on the host, the ``checkpoint()`` call included
on a save step), over at least 200 steps.  In a cell whose saves overlap
the window the tail sits among the steps beside the save's background
work and swings from run to run, so there it is a per-layer reading that
moves tokens_per_s, not a bound."""
from harness import stats

MIN_STEPS = 200


def read(run):
    times = run.get("step_times") or []
    if len(times) < MIN_STEPS:
        return None
    return stats.percentile(times, 95) * 1e3
