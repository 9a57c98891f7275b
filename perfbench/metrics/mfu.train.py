"""mfu.train: model FLOPs of the window's steps per second (6 x active
non-embedding parameters x tokens/s, ``harness.flops``) over the bf16 peak
of the cell's chips.  Moves tokens_per_s."""
from harness import readings


def read(run):
    p = readings.peak(run, "bf16_flops")
    if p is None or not run.get("tokens_per_s"):
        return None
    return 100.0 * run["flops_per_token"] * run["tokens_per_s"] \
        / (run.get("chips", 1) * p)
