"""Helpers the metric readers share.  A reader that finds nothing to read
returns None, and the metric is left out of the result line."""
from __future__ import annotations

from harness import peaks, stats

#: device programs of the checksum kernel hold this in their name
CHECKSUM_PROGRAM = "checksum"


def peak(run, what: str):
    if not run.get("on_chip"):
        return None
    return peaks.lookup(run["device_kind"])[what]


def idle_share(run):
    t = run.get("trace")
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def checksum_ms(run, times: int, digests_each: int):
    """Milliseconds the checksum programs ran on the device, per save or
    restore, over ``times`` of them in the trace; None unless the trace
    holds exactly their ``digests_each`` digests each."""
    t = run.get("trace")
    if not run.get("on_chip") or t is None or not times:
        return None
    count, seconds = t.module(CHECKSUM_PROGRAM)
    if count != digests_each * times or seconds <= 0:
        return None
    return 1e3 * seconds / times


def mean_of(rows, key):
    vals = [r[key] for r in rows if r.get(key) is not None]
    return stats.mean(vals) if vals else None
