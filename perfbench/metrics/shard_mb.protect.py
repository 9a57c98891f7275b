"""shard_mb.protect: the serialized shard each save writes
(``results["shard_bytes"]``), in MB, mean over the window's saves.  A
count: it moves protect_s by the bytes the background levels carry."""
from harness import readings


def read(run):
    v = readings.mean_of(run.get("saves") or [], "shard_bytes")
    return None if v is None else v / 1e6
