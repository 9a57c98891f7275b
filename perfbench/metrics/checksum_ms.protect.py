"""checksum_ms.protect: device time of the checksum kernel per save, from
the trace: a save digests every region and then the whole shard.  Moves
protect_s."""
from harness import readings


def read(run):
    saves = run.get("saves") or []
    return readings.checksum_ms(run, len(saves),
                                len(run.get("region_bytes") or ()) + 1)
