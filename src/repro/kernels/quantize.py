"""Pallas TPU kernels: block-wise int8 quantize / dequantize (VELOC
compression module for lossy checkpoint compression, 2-4x size reduction).

Each row of ``block_size`` values gets an absmax scale: q = round(x/s),
s = absmax/127.  Streaming, bandwidth-bound; tiles of ``BLOCK_ROWS`` rows
keep the working set in VMEM.  The scales are a 1-D f32 vector, which the
TPU lays out in 1024-element tiles, so a tile spans 1024 rows and its
scale block is one whole layout tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_SIZE = 256  # values per quantization block (one scale each)
BLOCK_ROWS = 1024  # 1024 x 256 x 4B = 1 MiB per f32 tile


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:, :].astype(jnp.float32)  # (rows, block_size)
    absmax = jnp.max(jnp.abs(x), axis=1)
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    q_ref[:, :] = q
    s_ref[:] = scale


def quantize_pallas(x: jax.Array, *, block_rows: int = BLOCK_ROWS,
                    interpret: bool = True):
    """x: (n_blocks, block_size) float -> (q int8 same shape, scales (n,) f32)."""
    n, bs = x.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0, (n, block_rows)
    return pl.pallas_call(
        _quant_kernel,
        out_shape=(jax.ShapeDtypeStruct((n, bs), jnp.int8),
                   jax.ShapeDtypeStruct((n,), jnp.float32)),
        grid=(n // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, bs), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((block_rows, bs), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows,), lambda i: (i,))),
        interpret=interpret,
    )(x)


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[:, :].astype(jnp.float32)
    o_ref[:, :] = q * s_ref[:][:, None]


def dequantize_pallas(q: jax.Array, scales: jax.Array, *,
                      block_rows: int = BLOCK_ROWS, interpret: bool = True):
    n, bs = q.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0
    return pl.pallas_call(
        _dequant_kernel,
        out_shape=jax.ShapeDtypeStruct((n, bs), jnp.float32),
        grid=(n // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, bs), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block_rows, bs), lambda i: (i, 0)),
        interpret=interpret,
    )(q, scales)
