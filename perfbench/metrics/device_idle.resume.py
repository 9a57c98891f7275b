"""device_idle.resume: 1 - (union of the device's busy intervals) / (traced
window), from the profiler trace of the recoveries.  Moves resume_s."""
from harness import readings


def read(run):
    return readings.idle_share(run)
