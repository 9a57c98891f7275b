"""checksum_ms.resume: as checksum_ms.protect, for the digests a restore
verifies (every region and the whole shard), per recovery.  Moves
resume_s."""
from harness import readings


def read(run):
    recs = run.get("recoveries") or []
    return readings.checksum_ms(run, len(recs),
                                len(run.get("region_bytes") or ()) + 1)
