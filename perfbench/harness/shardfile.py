"""Reads a checkpoint shard as the bytes a tier holds, with no code of the
program: an 8-byte magic, a little-endian uint64 header length, a JSON
header listing every region (name, dtype, shape, encoding, offset, nbytes)
and the payload.  Only raw regions are compared here: the cells write raw
shards."""
from __future__ import annotations

import json
import struct

import numpy as np

MAGIC = b"VELOCJX1"


def regions(blob) -> list[dict]:
    """The header's region table; raises on a blob that is no shard."""
    if blob is None:
        raise ValueError("no blob")
    view = memoryview(blob)
    if bytes(view[:8]) != MAGIC:
        raise ValueError("bad shard magic")
    (hlen,) = struct.unpack("<Q", view[8:16])
    header = json.loads(bytes(view[16:16 + hlen]).decode())
    base = 16 + hlen
    out = []
    for r in header["regions"]:
        r = dict(r)
        r["start"] = base + int(r["offset"])
        out.append(r)
    return out


def mismatches(blob, arrays: list) -> list[str]:
    """Names of the regions whose bytes differ from ``arrays`` (the
    snapshot's leaves, host copies, in the tree's leaf order), plus a note
    for each missing or surplus region."""
    try:
        table = regions(blob)
    except (ValueError, KeyError, json.JSONDecodeError) as e:
        return [f"unreadable: {e}"]
    bad = []
    if len(table) != len(arrays):
        bad.append(f"{len(table)} regions for {len(arrays)} leaves")
    view = memoryview(blob)
    for r, arr in zip(table, arrays):
        arr = np.ascontiguousarray(arr)
        want = arr.view(np.uint8).reshape(-1)
        if (r.get("encoding") != "raw" or tuple(r["shape"]) != arr.shape
                or np.dtype(r["dtype"]) != arr.dtype
                or int(r["nbytes"]) != want.size):
            bad.append(r["name"])
            continue
        got = np.frombuffer(view, np.uint8, want.size, r["start"])
        if not np.array_equal(got, want):
            bad.append(r["name"])
    return bad
