"""save_block_ms: the blocking part of ``VelocClient.checkpoint``, as the
program measures it (``results["app_blocking_s"]``), mean over the window's
saves.  Moves step_p95_ms."""
from harness import readings


def read(run):
    v = readings.mean_of(run.get("saves") or [], "app_blocking_s")
    return None if v is None else v * 1e3
