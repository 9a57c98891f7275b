"""Device-side L1 capture (DeepFreeze on TPU, DESIGN.md §2).

Two capture paths:

  1. **fused** — ``make_train_step(cfg, capture=True)`` makes the snapshot an
     output of the XLA training program itself, so the HBM copy overlaps
     with backward/optimizer compute (the execution-graph augmentation of
     DeepFreeze).  Cost: one extra params+opt copy in HBM.
  2. **standalone** — :func:`snapshot_device`, a jitted tree copy usable with
     any step function (the paper's baseline "blocking L1 memcpy"; still an
     HBM-bandwidth operation, ~12 ms for 10 GB/chip on v5e).

``iter_host_regions`` is the D2H stage the ActiveBackend drains: it walks
the snapshot's *addressable* shards (each host only touches bytes it owns —
the "every host writes its own shard" rule) and yields them as VELOC
regions, chunk-sized for the rate limiter.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import concurrency
from repro.core import delta as dlt
from repro.core.format import Region
from repro.core.spans import span
from repro.kernels import ops as kops


@jax.jit
def snapshot_device(state):
    """Explicit device-side copy of a pytree (standalone L1 capture)."""
    return jax.lax.optimization_barrier(
        jax.tree.map(lambda x: x + jnp.zeros((), x.dtype), state))


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


# ---------------------------------------------------------------------------
# device-side dirty tracking (fused fingerprint-diff-gather capture)
# ---------------------------------------------------------------------------


@dataclass
class DevicePlan:
    """One region's device-side diff plan.  The word tiling and the new
    fingerprints stay in HBM until the pipeline's dirty-ratio decision picks
    ``gather`` (ship only dirty chunks) or ``materialize`` (ship it all)."""

    key: tuple              # (stream, region name) — capture state key
    leaf: Any               # the device array (fully addressable)
    words: Any              # (rows_pad, chunk_words) uint32, device
    new_fp: Any             # (rows_pad, 2) uint32, device
    n_words: int
    rows: int               # unpadded chunk count (== DeltaPatch.n_chunks)
    nbytes: int
    chunk_bytes: int
    dirty_idx: np.ndarray   # (n_dirty,) int64 sorted ascending
    dirty_bytes: int        # exact bytes a delta of this plan would carry
    full: bool              # first version / shape change / forced full


class DeviceDeltaCapture:
    """HBM-resident dirty tracking across checkpoints (the fused
    fingerprint-diff-gather capture path).

    Holds each protected leaf's previous block fingerprints ON DEVICE, so a
    checkpoint's dirty detection is one fused Pallas pass (hash + compare,
    no fingerprint ever crosses PCIe) followed by a device-side gather that
    packs the dirty chunks contiguously — the D2H copy then moves
    ``dirty_ratio * bytes``, not ``bytes``.  Fingerprints are keyed by
    (stream, region name) and invalidated on any shape/dtype/topology change
    (elastic restart), which falls back to a full transfer + fresh
    fingerprints — never a wrong diff.

    Thread safety: ``plan`` / ``gather`` / ``materialize`` / ``commit`` for
    one stream must run under DeltaModule's per-stream lock (two racing
    versions of a stream must not diff against the same fingerprints — the
    same contract as the host tracker).  The state dict and the transfer
    counters get their own leaf guard because several streams may share one
    capture.

    ``stats`` counts the bytes this capture actually converts device→host
    (mask + fingerprints + checksum tables + gathered or materialized
    payloads).  On CPU the Pallas kernels run in interpret mode and "D2H"
    is a memcpy, but the counters measure the same transfers a TPU backend
    would issue — they are what bench_device_delta reports."""

    def __init__(self, chunk_bytes: int = dlt.DEFAULT_CHUNK_BYTES):
        self.chunk_bytes = int(chunk_bytes)
        self._fps: dict[tuple, Any] = {}     # key -> device fingerprints
        self._meta: dict[tuple, tuple] = {}  # key -> (shape, dtype)
        self._guard = concurrency.TrackedLock(
            "capture._guard", concurrency.RANK_GUARD)
        self.stats = {"planned": 0, "gathered": 0, "materialized": 0,
                      "fresh_full": 0, "d2h_bytes": 0,
                      "d2h_gather_bytes": 0, "d2h_full_bytes": 0}

    def _count(self, **deltas):
        with self._guard:
            for k, v in deltas.items():
                self.stats[k] += int(v)

    # -- eligibility -----------------------------------------------------
    def eligible(self, leaf) -> bool:
        """Device path supported: a non-empty, fully-addressable jax.Array
        whose dtype the device word builder covers (itemsize 1/2/4; bool
        and object-ish kinds excluded).  Everything else — multi-shard
        leaves, host arrays, exotic dtypes — keeps the host path."""
        if not isinstance(leaf, jax.Array) \
                or not hasattr(leaf, "addressable_shards"):
            return False
        dt = np.dtype(leaf.dtype)
        return leaf.size > 0 and dt.itemsize in (1, 2, 4) \
            and dt.kind not in ("b", "O", "c")

    # -- per-checkpoint protocol ----------------------------------------
    def plan(self, stream, name: str, leaf, *,
             force_full: bool = False) -> DevicePlan:
        """Fused fingerprint + diff of one region in HBM.  Only the
        chunk-sized dirty mask crosses to host; the decision of whether the
        chunks follow is the caller's (dirty-ratio cutoff)."""
        key = (stream, name)
        words, n_words, rows = kops.device_words(leaf, self.chunk_bytes)
        nbytes = int(leaf.size) * np.dtype(leaf.dtype).itemsize
        meta = (tuple(leaf.shape), str(leaf.dtype))
        with self._guard:
            prev = self._fps.get(key)
            fresh = prev is None or self._meta.get(key) != meta \
                or tuple(prev.shape) != (words.shape[0], 2)
        if force_full or fresh:
            new_fp = kops.device_fingerprints(words)
            dirty_idx = np.arange(rows, dtype=np.int64)
            dirty_bytes = nbytes
        else:
            new_fp, mask_dev = kops.fingerprint_diff(words, prev)
            mask = np.asarray(mask_dev)
            self._count(d2h_bytes=mask.nbytes)
            dirty_idx = np.nonzero(mask[:rows, 0])[0].astype(np.int64)
            dirty_bytes = len(dirty_idx) * self.chunk_bytes
            if len(dirty_idx) and int(dirty_idx[-1]) == rows - 1:
                # short tail chunk counts its real bytes
                dirty_bytes += (nbytes - (rows - 1) * self.chunk_bytes) \
                    - self.chunk_bytes
        self._count(planned=1, fresh_full=int(fresh and not force_full))
        return DevicePlan(key=key, leaf=leaf, words=words, new_fp=new_fp,
                          n_words=n_words, rows=rows, nbytes=nbytes,
                          chunk_bytes=self.chunk_bytes, dirty_idx=dirty_idx,
                          dirty_bytes=dirty_bytes,
                          full=bool(force_full or fresh))

    def host_fp(self, plan: DevicePlan) -> np.ndarray:
        """Host copy of the plan's new fingerprints (tracker state; a few
        bytes per chunk)."""
        fp = np.asarray(plan.new_fp)
        self._count(d2h_bytes=fp.nbytes)
        return fp[:plan.rows]

    def gather(self, plan: DevicePlan) -> dlt.PrecomputedDiff:
        """Pack the plan's dirty chunks contiguously ON DEVICE, copy only
        them to host, and emit the precomputed diff ``make_patch`` packs
        verbatim.  The dirty index vector is padded to the next power of
        two (repeating the last index) so the gather kernel sees a bounded
        set of shapes — at most 2x the dirty bytes cross PCIe, and the
        padding is trimmed before the patch is built."""
        cb = plan.chunk_bytes
        k = int(len(plan.dirty_idx))
        if k == 0:
            data: bytes = b""
            digests: list = []
        else:
            idx = plan.dirty_idx
            n_pad = 1 << (k - 1).bit_length()
            if n_pad > k:
                idx = np.concatenate(
                    [idx, np.full(n_pad - k, idx[-1], np.int64)])
            host = np.asarray(kops.gather_rows(plan.words, idx))
            self._count(gathered=1, d2h_bytes=host.nbytes,
                        d2h_gather_bytes=host.nbytes)
            u8 = host[:k].view(np.uint8).reshape(-1)
            tail = plan.nbytes - (plan.rows - 1) * cb
            views = [u8[t * cb:t * cb
                        + (cb if int(i) < plan.rows - 1 else tail)]
                     for t, i in enumerate(plan.dirty_idx)]
            digests = kops.chunk_digests(views)
            # dirty rows are already contiguous; only a short tail (always
            # last) needs trimming — one copy of the dirty bytes, total.
            data = u8[:int(sum(v.shape[0] for v in views))].tobytes()
        # full-array digest WITHOUT the full array: checksum the device
        # word tiling in place; only the (rows, 2) table crosses PCIe.
        table = kops.fletcher_chunks(plan.words.reshape(-1))
        self._count(d2h_bytes=table.nbytes)
        return dlt.PrecomputedDiff(
            shape=tuple(plan.leaf.shape), dtype=str(plan.leaf.dtype),
            nbytes=plan.nbytes, chunk_bytes=cb,
            indices=plan.dirty_idx, data=data, chunk_digests=digests,
            full_digest=kops.fold_digest(table, plan.n_words),
            fps=self.host_fp(plan))

    def materialize(self, plan: DevicePlan) -> np.ndarray:
        """Full D2H copy of the region (full checkpoint, mostly-dirty
        cutoff, or first version) — the honest fallback the counters keep
        visible."""
        arr = np.ascontiguousarray(np.asarray(plan.leaf))
        self._count(materialized=1, d2h_bytes=arr.nbytes,
                    d2h_full_bytes=arr.nbytes)
        return arr

    def commit(self, plan: DevicePlan):
        """Adopt the plan's fingerprints as the leaf's device-resident
        state (call once the version's diff decision is final, under the
        same per-stream lock that planned it)."""
        with self._guard:
            self._fps[plan.key] = plan.new_fp
            self._meta[plan.key] = (tuple(plan.leaf.shape),
                                    str(plan.leaf.dtype))

    def invalidate(self, stream=None):
        """Drop device fingerprints (all streams, or one) — e.g. after an
        elastic restart re-shards the state."""
        with self._guard:
            if stream is None:
                self._fps.clear()
                self._meta.clear()
                return
            for key in [k for k in self._fps if k[0] == stream]:
                self._fps.pop(key, None)
                self._meta.pop(key, None)


def iter_host_regions(snap, *, rank_prefix: str = "",
                      device_delta: Optional[DeviceDeltaCapture] = None
                      ) -> Iterator[Region]:
    """Yield one Region per (leaf, addressable shard).  Region names encode
    the tree path + shard index; global layout metadata enables elastic
    re-sharding on restart.

    With ``device_delta``, fully-addressable single-shard/replicated leaves
    the capture supports are yielded UNMATERIALIZED (``array=None`` with
    ``leaf``/``capture`` set): the delta module fingerprints and diffs them
    in HBM and only dirty chunks cross PCIe.  Multi-shard leaves, host
    leaves, and unsupported dtypes keep the materializing host path — the
    full-yield fallback on reshard or topology change."""
    leaves = jax.tree_util.tree_leaves_with_path(snap)
    for path, leaf in leaves:
        name = rank_prefix + _path_str(path)
        if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
            shards = leaf.addressable_shards
            if shards[0].data.shape == leaf.shape:  # replicated or 1 device
                data = shards[0].data
                if device_delta is not None and device_delta.eligible(data):
                    yield Region(name=name, array=None,
                                 global_shape=tuple(leaf.shape),
                                 leaf=data, capture=device_delta)
                else:
                    yield Region(name=name, array=np.asarray(data),
                                 global_shape=tuple(leaf.shape))
                continue
            seen = set()
            for sh in shards:
                idx = sh.index  # tuple of slices into the global array
                starts = tuple(0 if s.start is None else s.start for s in idx)
                if starts in seen:  # replicated copy of the same slice
                    continue
                seen.add(starts)
                yield Region(
                    name=f"{name}@" + ",".join(str(s) for s in starts),
                    array=np.asarray(sh.data),
                    global_shape=tuple(leaf.shape))
        else:
            yield Region(name=name, array=np.asarray(leaf),
                         global_shape=tuple(np.shape(leaf)))


def host_state_bytes(snap) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(snap)
               if hasattr(l, "dtype"))


def tree_from_regions(template, regions: dict[str, np.ndarray],
                      shardings=None):
    """Rebuild a pytree from {path: array}; device_put with shardings when
    given (restart path)."""
    with span("restore.place"):
        leaves_p = jax.tree_util.tree_leaves_with_path(template)
        treedef = jax.tree.structure(template)
        flat_shard = None if shardings is None else jax.tree.leaves(shardings)
        out = []
        for i, (path, leaf) in enumerate(leaves_p):
            name = _path_str(path)
            if name in regions:
                arr = regions[name]
            else:
                # reassemble from per-shard pieces ("name@start0,start1,...")
                prefix = name + "@"
                pieces = {k: v for k, v in regions.items() if k.startswith(prefix)}
                if not pieces:
                    raise KeyError(f"region {name!r} missing from checkpoint")
                shape = leaf.shape if hasattr(leaf, "shape") else np.shape(leaf)
                arr = np.zeros(shape, dtype=pieces[next(iter(pieces))].dtype)
                for k, piece in pieces.items():
                    suffix = k[len(prefix):]
                    starts = tuple(int(s) for s in suffix.split(",")) if suffix \
                        else ()
                    sl = tuple(slice(s, s + d) for s, d in zip(starts, piece.shape))
                    arr[sl] = piece
            want_dtype = leaf.dtype if hasattr(leaf, "dtype") else np.asarray(leaf).dtype
            arr = np.asarray(arr).astype(want_dtype, copy=False).reshape(
                leaf.shape if hasattr(leaf, "shape") else np.shape(leaf))
            with span("restore.device_put", bytes=arr.nbytes):
                if flat_shard is not None:
                    out.append(jax.device_put(arr, flat_shard[i]))
                else:
                    out.append(jnp.asarray(arr))
        return jax.tree.unflatten(treedef, out)
