"""restore_call_s: the benchmark's span around ``restart_latest`` (plan,
read, verify, assemble on the host, ``device_put``), mean over the
window's recoveries.  Moves resume_s."""
from harness import readings


def read(run):
    return readings.mean_of(run.get("recoveries") or [], "restore_call_s")
