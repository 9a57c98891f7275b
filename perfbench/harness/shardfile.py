"""Reads a checkpoint shard as the bytes a tier holds, with no code of the
program: an 8-byte magic, a little-endian uint64 header length, a JSON
header listing every region (name, dtype, shape, global shape, encoding,
offset, nbytes) and the payload.  Only raw regions are compared here: the cells write raw
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


def _leaf_groups(table: list[dict]) -> list[list[dict]]:
    """The regions of each leaf, in the tree's leaf order: a region whose
    name has no ``@`` is a whole leaf; consecutive regions named
    ``<leaf>@<starts>`` are the shards of one leaf."""
    groups, last = [], None
    for r in table:
        base, at, _ = r["name"].partition("@")
        if at and base == last:
            groups[-1].append(r)
        else:
            groups.append([r])
        last = base if at else None
    return groups


def _starts(r: dict) -> tuple:
    _, at, starts = r["name"].partition("@")
    return tuple(int(s) for s in starts.split(",")) if at else ()


def mismatches(blob, arrays: list) -> list[str]:
    """Names of the regions whose bytes differ from ``arrays`` (the
    snapshot's leaves, whole host copies, in the tree's leaf order), plus
    a note for each missing or surplus region.  A region named
    ``<leaf>@<starts>`` holds one shard: it is compared with the slice of
    its leaf at those starts, and its leaf's shards have to cover the
    leaf (the header's ``global_shape``) once."""
    try:
        table = regions(blob)
    except (ValueError, KeyError, json.JSONDecodeError) as e:
        return [f"unreadable: {e}"]
    bad = []
    groups = _leaf_groups(table)
    if len(groups) != len(arrays):
        bad.append(f"{len(groups)} regions for {len(arrays)} leaves")
    view = memoryview(blob)
    for group, arr in zip(groups, arrays):
        arr = np.ascontiguousarray(arr)
        covered = 0
        for r in group:
            starts = _starts(r)
            part = arr
            if starts:
                if (tuple(r.get("global_shape", ())) != arr.shape
                        or len(starts) != arr.ndim):
                    bad.append(r["name"])
                    continue
                part = np.ascontiguousarray(arr[tuple(
                    slice(s, s + n) for s, n in zip(starts, r["shape"]))])
            want = part.view(np.uint8).reshape(-1)
            if (r.get("encoding") != "raw" or tuple(r["shape"]) != part.shape
                    or np.dtype(r["dtype"]) != part.dtype
                    or int(r["nbytes"]) != want.size):
                bad.append(r["name"])
                continue
            covered += part.size
            got = np.frombuffer(view, np.uint8, want.size, r["start"])
            if not np.array_equal(got, want):
                bad.append(r["name"])
        if _starts(group[0]) and covered != arr.size:
            bad.append(f"{group[0]['name'].partition('@')[0]}: shards "
                       f"cover {covered} of {arr.size} elements")
    return bad
