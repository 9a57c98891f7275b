"""device_idle.train: 1 - (union of the device's busy intervals) / (traced
window), from the profiler trace of a training window.  Moves
tokens_per_s."""
from harness import readings


def read(run):
    return readings.idle_share(run)
