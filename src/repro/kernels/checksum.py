"""Pallas TPU kernel: chunked Fletcher-style checksum (VELOC integrity module).

Per chunk of ``chunk`` uint32 words computes the pair
  c1 = sum(x_i)            (mod 2^32, natural uint32 wraparound)
  c2 = sum((i+1) * x_i)    (mod 2^32)
which detects both corruption and word reordering.  The grid walks chunk
rows in tiles of ``row_block`` rows; the position weights are generated
in-kernel with a broadcasted iota (VREG-friendly, no HBM traffic for weights).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK_WORDS = 2048
BLOCK_ROWS = 64  # row padding unit of the ops wrappers; row_block divides it
TILE_WORDS = 128 * 1024  # words per input tile: 512 KiB, double-buffered


def row_block(n: int, chunk: int) -> int:
    """Rows per grid step for an (n, chunk) input: about ``TILE_WORDS``
    words, a multiple of 8 sublanes that divides ``BLOCK_ROWS`` (so it
    divides every row count the ops wrappers pad to), or all ``n`` rows when
    ``n`` is a single short tile."""
    rb = max(8, min(BLOCK_ROWS, TILE_WORDS // chunk))
    rb = 1 << (rb.bit_length() - 1)
    return rb if n % rb == 0 else n


def _sum_u32(x):
    """Row sums of a uint32 tile mod 2^32, as a (rows, 1) column.  Mosaic
    has no unsigned reductions; int32 addition wraps identically mod 2^32,
    so summing the bitcast words gives the same bits."""
    s = jnp.sum(jax.lax.bitcast_convert_type(x, jnp.int32), axis=1,
                keepdims=True)
    return jax.lax.bitcast_convert_type(s, jnp.uint32)


def _checksum_kernel(x_ref, o_ref):
    x = x_ref[:, :]  # (rows, chunk) uint32
    w = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1) + jnp.uint32(1)
    o_ref[:, :] = jnp.concatenate([_sum_u32(x), _sum_u32(x * w)], axis=1)


def _row_call(kernel, x, *side, out_cols, block_rows, interpret):
    """One grid walk over row tiles of ``x`` (n, chunk), the shared launcher
    of the row kernels: every side input and every output is an (n, c)
    uint32 table tiled by the same rows.  Returns the list of outputs."""
    n, chunk = x.shape
    rb = block_rows or row_block(n, chunk)
    if n % rb:
        rb = n

    def spec(cols):
        return pl.BlockSpec((rb, cols), lambda i: (i, 0))

    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n, c), jnp.uint32) for c in out_cols],
        grid=(n // rb,),
        in_specs=[spec(chunk)] + [spec(t.shape[1]) for t in side],
        out_specs=[spec(c) for c in out_cols],
        interpret=interpret,
    )(x, *side)


def checksum_pallas(x: jax.Array, *, block_rows: int | None = None,
                    interpret: bool = True) -> jax.Array:
    """x: (n_chunks, chunk_words) uint32 -> (n_chunks, 2) uint32."""
    return _row_call(_checksum_kernel, x, out_cols=(2,),
                     block_rows=block_rows, interpret=interpret)[0]


# ---------------------------------------------------------------------------
# block fingerprints (incremental-checkpoint dirty detection)
# ---------------------------------------------------------------------------

#: odd multiplicative constants (xxhash/Murmur finalizer family) — uint32
#: wraparound multiplication mixes every input bit into the high bits, which
#: the weighted Fletcher sums above don't (a flipped low bit in two words can
#: cancel).  Dirty detection needs per-chunk avalanche, not just order
#: sensitivity.
_MIX1 = 0x9E3779B1
_MIX2 = 0x85EBCA77
_MIX3 = 0xC2B2AE3D


def _blockhash_rows(x):
    """Per-row mixed fingerprint pair of a (rows, chunk) uint32 tile as a
    (rows, 2) table — the shared body of the plain and fused-diff block-hash
    kernels (both must emit bit-identical fingerprints)."""
    i = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    # per-word avalanche, then two independent position-weighted reductions
    y = (x ^ (x >> 15)) * jnp.uint32(_MIX1)
    y = (y ^ (y >> 13)) * jnp.uint32(_MIX2)
    y = y ^ (y >> 16)
    w1 = i * jnp.uint32(2) + jnp.uint32(1)              # odd weights
    w2 = (i + jnp.uint32(1)) * jnp.uint32(_MIX3) | jnp.uint32(1)
    return jnp.concatenate([_sum_u32(y * w1), _sum_u32((y ^ w2) * w2)], axis=1)


def _blockhash_kernel(x_ref, o_ref):
    o_ref[:, :] = _blockhash_rows(x_ref[:, :])


def blockhash_pallas(x: jax.Array, *, block_rows: int | None = None,
                     interpret: bool = True) -> jax.Array:
    """x: (n_chunks, chunk_words) uint32 -> (n_chunks, 2) uint32 mixed
    fingerprints (64 collision bits per chunk)."""
    return _row_call(_blockhash_kernel, x, out_cols=(2,),
                     block_rows=block_rows, interpret=interpret)[0]


# ---------------------------------------------------------------------------
# fused fingerprint + diff (device-side dirty tracking)
# ---------------------------------------------------------------------------


def _blockhash_diff_kernel(x_ref, prev_ref, fp_ref, dirty_ref):
    fp = _blockhash_rows(x_ref[:, :])
    fp_ref[:, :] = fp
    ne = (fp != prev_ref[:, :]).astype(jnp.uint32)  # prev resident in HBM
    dirty_ref[:, :] = ne[:, 0:1] | ne[:, 1:2]


def blockhash_diff_pallas(x: jax.Array, prev_fp: jax.Array, *,
                          block_rows: int | None = None,
                          interpret: bool = True
                          ) -> tuple[jax.Array, jax.Array]:
    """Fused dirty detection: block-hash ``x`` AND compare against the
    previous fingerprints in one grid walk.

    x: (n_chunks, chunk) uint32, prev_fp: (n_chunks, 2) uint32 ->
    (new_fp (n_chunks, 2) uint32, dirty (n_chunks, 1) uint32 0/1).

    The fingerprint inputs never leave device memory — only the chunk-sized
    dirty mask (and whatever chunks it selects) need to cross PCIe."""
    assert prev_fp.shape == (x.shape[0], 2), (prev_fp.shape, x.shape)
    fp, dirty = _row_call(_blockhash_diff_kernel, x, prev_fp,
                          out_cols=(2, 1), block_rows=block_rows,
                          interpret=interpret)
    return fp, dirty


def _gather_rows_kernel(idx_ref, x_ref, o_ref):
    del idx_ref  # consumed by the index map (scalar prefetch)
    o_ref[...] = x_ref[...]


def gather_rows_pallas(x: jax.Array, idx: jax.Array, *,
                       interpret: bool = True) -> jax.Array:
    """Device-side compaction: pack rows ``idx`` of ``x`` contiguously.

    x: (n_chunks, chunk), idx: (n_out,) int32 -> (n_out, chunk).  The index
    vector rides in scalar-prefetch memory, so the grid walk DMAs exactly
    the selected chunk rows — the D2H transfer of the result is
    ``dirty_ratio * bytes``, not ``bytes``.

    A single row is not a legal TPU block of a 2-D array (the last two
    block dims must be 8x128 multiples or whole), so each row is viewed as
    a (chunk // 128, 128) slab and the row axis is squeezed out of the
    block; chunks that are not 128-word multiples use a (1, chunk) slab."""
    n_out = int(idx.shape[0])
    n, chunk = x.shape
    lanes = 128 if chunk % 128 == 0 else chunk
    slab = (chunk // lanes, lanes)
    out = pl.pallas_call(
        _gather_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_out,),
            in_specs=[pl.BlockSpec((None,) + slab,
                                   lambda i, idx_ref: (idx_ref[i], 0, 0))],
            out_specs=pl.BlockSpec((None,) + slab,
                                   lambda i, idx_ref: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_out,) + slab, x.dtype),
        interpret=interpret,
    )(idx, x.reshape((n,) + slab))
    return out.reshape(n_out, chunk)
