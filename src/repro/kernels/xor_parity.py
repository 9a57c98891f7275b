"""Pallas TPU kernel: XOR parity over K data blocks (VELOC L2 erasure encode).

RAID-5-style parity: ``parity[n] = x[0,n] ^ x[1,n] ^ ... ^ x[K-1,n]`` over
uint32 words.  Tiling: the grid walks the word axis in tiles of
``block_words(K)`` (1024-word aligned, the TPU layout of a 1-D uint32
array); each tile loads the full K rows (K is small — the erasure group
size, typically 2-16) and reduces in VREGs.

Also provides the pairwise kernel used by the ring reduce-scatter encode
(one XOR per collective-permute step).
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

LANE_WORDS = 1024  # tile alignment of a 1-D uint32 array on TPU
BLOCK_N = 262_144  # words per xor_pair tile (1 MiB per operand)
#: scoped-VMEM budget of one xor_reduce grid step.  Every block is double
#: buffered, and the (K, n) input block pads K up to whole 8-row sublane
#: tiles, so a step holds 2 * 4 * (ceil8(K) + 1) bytes per word; 8 MiB keeps
#: that within v5e's 16 MiB default scoped limit with room for the
#: accumulator at every K up to 16 (and beyond, with narrower tiles).
VMEM_BUDGET = 8 << 20


def block_words(k: int) -> int:
    """xor_reduce tile width for a K-row stack: the largest power of two
    (>= LANE_WORDS) whose double-buffered blocks fit ``VMEM_BUDGET``."""
    per_word = 2 * 4 * (-(-k // 8) * 8 + 1)
    bn = LANE_WORDS
    while 2 * bn * per_word <= VMEM_BUDGET:
        bn *= 2
    return bn


def tile_words(n: int, cap: int) -> int:
    """Tile width for an n-word row: ``cap``, or for shorter rows n rounded
    up to the 1-D layout tile (one grid step, no padding up to ``cap``)."""
    return min(cap, -(-n // LANE_WORDS) * LANE_WORDS)


def _xor_reduce_kernel(x_ref, o_ref):
    acc = x_ref[0, :]
    for k in range(1, x_ref.shape[0]):
        acc = acc ^ x_ref[k, :]
    o_ref[:] = acc


def xor_reduce_pallas(x: jax.Array, *, block_n: int | None = None,
                      interpret: bool = True) -> jax.Array:
    """x: (K, N) uint32 with N a multiple of the tile -> (N,) parity.
    The tile (``block_words(K)`` unless given) clamps to N for small
    inputs, so it never exceeds the data."""
    K, N = x.shape
    block_n = min(block_n or block_words(K), N)
    assert N % block_n == 0, (N, block_n)
    return pl.pallas_call(
        _xor_reduce_kernel,
        out_shape=jax.ShapeDtypeStruct((N,), x.dtype),
        grid=(N // block_n,),
        in_specs=[pl.BlockSpec((K, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        interpret=interpret,
    )(x)


def _xor_pair_kernel(a_ref, b_ref, o_ref):
    o_ref[:] = a_ref[:] ^ b_ref[:]


def xor_pair_pallas(a: jax.Array, b: jax.Array, *, block_n: int = BLOCK_N,
                    interpret: bool = True) -> jax.Array:
    """a, b: (N,) uint32 with N a multiple of the tile (``block_n``, or N
    itself when shorter) -> a ^ b (ring reduce-scatter inner step)."""
    (N,) = a.shape
    block_n = min(block_n, N)
    assert N % block_n == 0, (N, block_n)
    return pl.pallas_call(
        _xor_pair_kernel,
        out_shape=jax.ShapeDtypeStruct((N,), a.dtype),
        grid=(N // block_n,),
        in_specs=[pl.BlockSpec((block_n,), lambda i: (i,)),
                  pl.BlockSpec((block_n,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
        interpret=interpret,
    )(a, b)
