"""Every Pallas kernel compiles for a TPU v5e, at the widths the chip runs,
and so does the device-delta word tiling that feeds them.

Nothing runs: the TPU compiler lowers each kernel for a described (not
attached) v5e chip, which refuses what interpret mode accepts — tilings
that break the 8x128 rule, layouts Mosaic cannot match, unsigned
reductions, blocks that overflow scoped VMEM.  The topology is described
inside a fixture, so only the worker that runs this file loads the TPU
library, and a host that cannot describe it skips these tests.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import checksum as ck
from repro.kernels import ops
from repro.kernels import quantize as qz
from repro.kernels import xor_parity as xp

CHUNK_64K = 64 * 1024 // 4  # words per 64 KiB delta chunk
XOR_WORDS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compile cache
    off: entries compiled for an absent chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _xor_case(k):
    return (lambda x: xp.xor_reduce_pallas(x, interpret=False),
            [((k, XOR_WORDS), jnp.uint32)])


CASES = {
    "checksum": (lambda x: ck.checksum_pallas(x, interpret=False),
                 [((512, ck.CHUNK_WORDS), jnp.uint32)]),
    # a digest's one program: a 3.74 GB shard's whole tiles and its tail
    "checksum_parts": (lambda b, t: ops._checksum_j(b, t, interpret=False),
                       [((456192, ck.CHUNK_WORDS), jnp.uint32),
                        ((16, ck.CHUNK_WORDS), jnp.uint32)]),
    "blockhash": (lambda x: ck.blockhash_pallas(x, interpret=False),
                  [((64, CHUNK_64K), jnp.uint32)]),
    "blockhash_diff": (
        lambda x, p: ck.blockhash_diff_pallas(x, p, interpret=False),
        [((64, CHUNK_64K), jnp.uint32), ((64, 2), jnp.uint32)]),
    "gather_rows": (lambda x, i: ck.gather_rows_pallas(x, i, interpret=False),
                    [((64, CHUNK_64K), jnp.uint32), ((8,), jnp.int32)]),
    "xor_pair": (lambda a, b: xp.xor_pair_pallas(a, b, interpret=False),
                 [((XOR_WORDS,), jnp.uint32)] * 2),
    "quantize": (lambda x: qz.quantize_pallas(x, interpret=False),
                 [((4096, qz.BLOCK_SIZE), jnp.float32)]),
    "dequantize": (lambda q, s: qz.dequantize_pallas(q, s, interpret=False),
                   [((4096, qz.BLOCK_SIZE), jnp.int8),
                    ((4096,), jnp.float32)]),
    # every erasure-group width XorGroupModule produces
    **{f"xor_reduce_k{k}": _xor_case(k) for k in range(2, 17)},
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = CASES[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, jnp.float32])
def test_device_words_compile_lean_for_v5e(dtype, one_chip):
    """The device-delta word tiling of a 64 MiB leaf of a narrow dtype
    keeps its temporaries within a few times the output's size: an
    (n, 4 // itemsize) view would pad its minor dim to 128 lanes on TPU
    (gigabytes of temporaries and a compile of minutes)."""
    nbytes = 64 << 20
    n = nbytes // jnp.dtype(dtype).itemsize + 3  # an unaligned tail too
    total = -(-(nbytes + 4) // (4 * CHUNK_64K)) * CHUNK_64K
    leaf = jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)
    compiled = ops._device_words_j.lower(leaf, total).compile()
    out_bytes = 4 * total
    assert compiled.memory_analysis().temp_size_in_bytes <= 4 * out_bytes
