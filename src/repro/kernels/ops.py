"""Jitted public wrappers over the Pallas kernels.

Handles arbitrary byte buffers: pad + reshape into kernel tiling, dispatch
(interpret mode on CPU, compiled on TPU, refused anywhere else), unpad.
These are the primitives the VELOC modules (checksum / compress /
erasure-encode) call.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.spans import span
from repro.kernels import checksum as _ck
from repro.kernels import quantize as _qz
from repro.kernels import xor_parity as _xp


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted: on the CPU backend
    (tests, host-only runs) they do; on a TPU they are compiled.  Any other
    backend has no Mosaic lowering and no business interpreting device
    work, so it is an error rather than a silent slow path."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels support the 'tpu' and 'cpu' backends, not "
        f"{platform!r}")


#: Lifetime kernel-dispatch counters (benchmarks and tests read deltas to
#: assert batching actually collapses per-chunk dispatches into one).
KERNEL_DISPATCHES = {"checksum": 0, "blockhash": 0, "gather": 0, "xor": 0}

#: Lifetime host-byte counters of ``digest``: bytes handed to the device as
#: a view of the caller's buffer, and bytes copied on the host first.
DIGEST_HOST_BYTES = {"viewed": 0, "copied": 0}


def _pad_last(x, total: int):
    """Zero-pad the last axis of ``x`` to ``total``: on the host for a
    NumPy array (so a new length costs no device compile), on the device
    for a device array."""
    pad = total - x.shape[-1]
    if not pad:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return np.pad(x, widths) if isinstance(x, np.ndarray) \
        else jnp.pad(x, widths)


def _is_contiguous(buf) -> bool:
    """Whether ``_byte_view`` reads ``buf`` in place, without a copy."""
    return isinstance(buf, (bytes, bytearray, memoryview)) or (
        isinstance(buf, np.ndarray) and buf.flags.c_contiguous)


def _byte_view(buf: bytes | np.ndarray) -> np.ndarray:
    """The bytes of ``buf`` as a flat uint8 array: a view when
    ``_is_contiguous(buf)``, else a contiguous copy."""
    if isinstance(buf, (bytes, bytearray, memoryview)):
        return np.frombuffer(buf, dtype=np.uint8)
    return np.ascontiguousarray(buf).reshape(-1).view(np.uint8)


def bytes_to_u32(buf: bytes | np.ndarray) -> np.ndarray:
    a = _byte_view(buf)
    pad = (-a.size) % 4
    if pad:
        a = np.concatenate([a, np.zeros(pad, np.uint8)])
    return a.view(np.uint32)


# ---------------------------------------------------------------------------
# XOR parity
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("interpret",))
def _xor_reduce_j(x, interpret=True):
    return _xp.xor_reduce_pallas(x, interpret=interpret)


def xor_reduce(x) -> np.ndarray:
    """x: (K, N) uint32 -> (N,) host parity (pads N to the tile size)."""
    K, n = x.shape
    KERNEL_DISPATCHES["xor"] += 1
    tile = _xp.tile_words(n, _xp.block_words(K))
    x = jnp.asarray(_pad_last(x, -(-n // tile) * tile))
    return np.asarray(_xor_reduce_j(x, interpret=interpret_mode()))[:n]


# ---------------------------------------------------------------------------
# checksums
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("interpret",))
def _checksum_j(*parts, interpret=True):
    """The checksum tables of one or more row tilings, in one program."""
    return tuple(_ck.checksum_pallas(x, interpret=interpret) for x in parts)


def padded_rows(rows: int) -> int:
    """Row count the row kernels run at for ``rows`` chunk rows: whole
    8-row sublane tiles up to one ``BLOCK_ROWS`` tile, whole tiles beyond —
    so ``checksum.row_block`` always finds a tile that divides it.  Zero
    rows fold as the identity (``fold_digest``) and are sliced off the
    fingerprint tables, so padding never changes a value."""
    unit = 8 if rows <= _ck.BLOCK_ROWS else _ck.BLOCK_ROWS
    return -(-rows // unit) * unit


def _row_tiling(words, chunk: int):
    """(rows, chunk) zero-padded tiling of a flat word vector plus its
    unpadded row count.  Host words are padded on the host, so each new
    buffer length costs no device compile."""
    rows = -(-words.shape[0] // chunk)
    words = _pad_last(words, padded_rows(rows) * chunk)
    return jnp.asarray(words.reshape(-1, chunk)), rows


def fletcher_chunks(words: jax.Array | np.ndarray,
                    chunk: int = _ck.CHUNK_WORDS) -> np.ndarray:
    """words: (n,) uint32 -> (n_chunks, 2) uint32 per-chunk checksums."""
    if words.shape[0] == 0:
        return np.zeros((0, 2), np.uint32)
    KERNEL_DISPATCHES["checksum"] += 1
    tiles, rows = _row_tiling(words, chunk)
    table, = _checksum_j(tiles, interpret=interpret_mode())
    return np.asarray(table)[:rows]


@partial(jax.jit, static_argnames=("interpret",))
def _blockhash_j(x, interpret=True):
    return _ck.blockhash_pallas(x, interpret=interpret)


def block_fingerprints(buf: bytes | np.ndarray,
                       chunk_bytes: int = 4 * _ck.CHUNK_WORDS) -> np.ndarray:
    """Per-chunk mixed fingerprints of a byte buffer: (n_chunks, 2) uint32.

    ``chunk_bytes`` must be a multiple of 4; the trailing partial chunk is
    zero-padded (same rule as the delta encoder, so fingerprints of the same
    logical chunk always agree)."""
    assert chunk_bytes % 4 == 0 and chunk_bytes > 0, chunk_bytes
    words = bytes_to_u32(buf)
    if words.shape[0] == 0:
        return np.zeros((0, 2), np.uint32)
    tiles, rows = _row_tiling(words, chunk_bytes // 4)
    KERNEL_DISPATCHES["blockhash"] += 1
    return np.asarray(_blockhash_j(tiles, interpret=interpret_mode()))[:rows]


def fold_digest(chunks: np.ndarray, n_words: int) -> str:
    """Fold a (n, 2) per-chunk checksum table into the canonical hex digest
    of a buffer of ``n_words`` uint32 words.  All-zero rows fold as the
    identity (xor 0 / + 0), so a table over a zero-padded tiling folds to
    the same digest as the unpadded buffer — what lets ``chunk_digests``
    and the device-side digest batch many buffers into one kernel pass."""
    chunks = np.asarray(chunks)
    h1 = np.bitwise_xor.reduce(chunks[:, 0]) if len(chunks) else np.uint32(0)
    h2 = np.uint32(np.sum(chunks[:, 1], dtype=np.uint64) & 0xFFFFFFFF) \
        if len(chunks) else np.uint32(0)
    return f"{int(h1):08x}{int(h2):08x}{int(n_words):08x}"


def _nbytes(buf) -> int:
    n = getattr(buf, "nbytes", None)   # arrays and memoryviews
    return len(buf) if n is None else n


#: bytes of one whole 64-row tile of checksum rows (512 KiB)
_TILE_BYTES = 4 * _ck.BLOCK_ROWS * _ck.CHUNK_WORDS


def digest(buf: bytes | np.ndarray) -> str:
    """Hex digest of a byte buffer (chunk checksums folded host-side).

    The buffer's whole 64-row tiles go to the device as a view of its
    bytes; only the tail after them (under one tile) is copied, into a
    zeroed tiling, so its last partial word reads little-endian with zero
    fill.  Both tilings are checksummed in one program.  Chunks are
    independent and zero rows fold as the identity (``fold_digest``), so
    the cut leaves the digest as if the whole zero-padded buffer were
    checksummed at once."""
    nbytes = _nbytes(buf)
    body = nbytes // _TILE_BYTES * _TILE_BYTES
    copied = nbytes - body + (0 if _is_contiguous(buf) else nbytes)
    with span("digest", bytes=nbytes, copied=copied):
        u8 = _byte_view(buf)
        parts = []
        if body:
            parts.append(u8[:body].view(np.uint32)
                         .reshape(-1, _ck.CHUNK_WORDS))
        if nbytes > body:
            rows = -(-(nbytes - body) // (4 * _ck.CHUNK_WORDS))
            tail = np.zeros((padded_rows(rows), _ck.CHUNK_WORDS), np.uint32)
            tail.view(np.uint8).reshape(-1)[:nbytes - body] = u8[body:]
            parts.append(tail)
        DIGEST_HOST_BYTES["viewed"] += body
        DIGEST_HOST_BYTES["copied"] += copied
        n_words = -(-nbytes // 4)
        if not parts:
            return fold_digest(np.zeros((0, 2), np.uint32), n_words)
        KERNEL_DISPATCHES["checksum"] += 1
        tables = _checksum_j(*map(jnp.asarray, parts),
                             interpret=interpret_mode())
        return fold_digest(np.concatenate([np.asarray(t) for t in tables]),
                           n_words)


def chunk_digests(blobs) -> list[str]:
    """``[digest(b) for b in blobs]`` in one checksum-kernel dispatch per
    distinct row count instead of one per buffer.

    Buffers are padded to whole 2048-word rows (zero rows fold as the
    identity, see ``fold_digest``), stacked by equal row count, and checksummed
    in a single grid walk per group — for a patch of N equal-size dirty
    chunks that is 1 dispatch, not N.  Byte-identical output to per-buffer
    ``digest``."""
    blobs = list(blobs)
    with span("digest", bytes=sum(_nbytes(b) for b in blobs)):
        out: list = [None] * len(blobs)
        words_of: list = [None] * len(blobs)
        groups: dict[int, list[int]] = {}
        for j, b in enumerate(blobs):
            w = bytes_to_u32(b)
            if w.shape[0] == 0:
                out[j] = fold_digest(np.zeros((0, 2), np.uint32), 0)
                continue
            words_of[j] = w
            rows = -(-w.shape[0] // _ck.CHUNK_WORDS)
            groups.setdefault(rows, []).append(j)
        for rows, members in groups.items():
            width = rows * _ck.CHUNK_WORDS
            stacked = np.zeros(len(members) * width, np.uint32)
            for slot, j in enumerate(members):
                w = words_of[j]
                stacked[slot * width:slot * width + w.shape[0]] = w
            table = fletcher_chunks(stacked)
            for slot, j in enumerate(members):
                out[j] = fold_digest(table[slot * rows:(slot + 1) * rows],
                                     words_of[j].shape[0])
        return out


# ---------------------------------------------------------------------------
# device-side dirty tracking (fused fingerprint-diff + gather, HBM-resident)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("total",))
def _device_words_j(flat, total):
    # little-endian: element j of each group of 4 // itemsize fills bits
    # [8 * itemsize * j, ...) of its word — bit-identical to host
    # bytes_to_u32 of the same bytes.  Strided 1-D slices, not an
    # (n, 4 // itemsize) view: on TPU that view pads its minor dim to 128
    # lanes, and a 64 MiB bf16 leaf then took ~90 s to compile for v5e.
    size = flat.dtype.itemsize
    ratio = 4 // size
    u = jax.lax.bitcast_convert_type(flat, jnp.dtype(f"uint{8 * size}"))
    pad = (-u.shape[0]) % ratio
    if pad:
        u = jnp.concatenate([u, jnp.zeros((pad,), u.dtype)])
    w = u[0::ratio].astype(jnp.uint32)
    for j in range(1, ratio):
        w = w | (u[j::ratio].astype(jnp.uint32) << (8 * size * j))
    if w.shape[0] < total:
        w = jnp.concatenate([w, jnp.zeros((total - w.shape[0],), jnp.uint32)])
    return w


def device_words(x, chunk_bytes: int):
    """Flatten a device array into the (rows, chunk_words) uint32 tiling the
    fingerprint kernels consume — entirely in HBM, byte-identical to
    ``bytes_to_u32`` of the host copy, zero-padded exactly like
    ``block_fingerprints``.  Returns ``(words2d, n_words, rows)`` where
    ``rows`` is the unpadded chunk count."""
    assert chunk_bytes % 4 == 0 and chunk_bytes > 0, chunk_bytes
    chunk = chunk_bytes // 4
    flat = x.reshape(-1)
    nbytes = int(flat.size) * flat.dtype.itemsize
    n_words = -(-nbytes // 4)
    rows = -(-n_words // chunk)
    rows_pad = padded_rows(rows)
    w = _device_words_j(flat, rows_pad * chunk)
    return w.reshape(rows_pad, chunk), n_words, rows


def device_fingerprints(words2d) -> jax.Array:
    """Block fingerprints of a device word tiling; the result STAYS on
    device (same kernel/values as ``block_fingerprints``, no D2H)."""
    KERNEL_DISPATCHES["blockhash"] += 1
    return _blockhash_j(words2d, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("interpret",))
def _blockhash_diff_j(x, prev, interpret=True):
    return _ck.blockhash_diff_pallas(x, prev, interpret=interpret)


def fingerprint_diff(words2d, prev_fp):
    """Fused fingerprint + dirty detection in one grid walk: returns
    ``(new_fp (rows, 2), dirty (rows, 1))`` — both device-resident, neither
    fingerprint input ever leaves HBM.  Only the chunk-sized dirty mask
    (and whatever chunks it selects) needs to cross PCIe."""
    KERNEL_DISPATCHES["blockhash"] += 1
    return _blockhash_diff_j(words2d, prev_fp, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("interpret",))
def _gather_j(x, idx, interpret=True):
    return _ck.gather_rows_pallas(x, idx, interpret=interpret)


def gather_rows(words2d, idx):
    """Device-side compaction: pack the selected chunk rows contiguously
    (scalar-prefetch gather kernel), so the subsequent D2H copy moves
    ``len(idx)`` chunks instead of the whole region."""
    KERNEL_DISPATCHES["gather"] += 1
    return _gather_j(words2d, jnp.asarray(idx, jnp.int32),
                     interpret=interpret_mode())


# ---------------------------------------------------------------------------
# block quantization (compression module)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("interpret",))
def _quant_j(x, interpret=True):
    return _qz.quantize_pallas(x, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def _dequant_j(q, s, interpret=True):
    return _qz.dequantize_pallas(q, s, interpret=interpret)


def quantize(x: np.ndarray | jax.Array):
    """x: any-shape float array -> (q int8 (rows, BLOCK_SIZE), scales f32,
    orig_len, shape).  Rows pad to whole ``BLOCK_ROWS`` kernel tiles."""
    shape = tuple(x.shape)
    flat = x.reshape(-1).astype(np.float32)
    n = flat.shape[0]
    rows = -(-n // _qz.BLOCK_SIZE)
    rows_pad = -(-rows // _qz.BLOCK_ROWS) * _qz.BLOCK_ROWS
    tiles = jnp.asarray(_pad_last(flat, rows_pad * _qz.BLOCK_SIZE))
    q, s = _quant_j(tiles.reshape(rows_pad, _qz.BLOCK_SIZE),
                    interpret=interpret_mode())
    return np.asarray(q)[:rows], np.asarray(s)[:rows], n, shape


def dequantize(q: np.ndarray, scales: np.ndarray, n: int, shape) -> np.ndarray:
    rows = q.shape[0]
    pad = -(-rows // _qz.BLOCK_ROWS) * _qz.BLOCK_ROWS - rows
    q = np.pad(q, ((0, pad), (0, 0)))
    scales = np.pad(scales, (0, pad))
    out = _dequant_j(jnp.asarray(q), jnp.asarray(scales),
                     interpret=interpret_mode())
    return np.asarray(out).reshape(-1)[:n].reshape(shape)
