"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.kernels import ops, ref
from repro.kernels.checksum import (CHUNK_WORDS, blockhash_pallas,
                                    checksum_pallas)
from repro.kernels.quantize import dequantize_pallas, quantize_pallas
from repro.kernels.xor_parity import xor_pair_pallas, xor_reduce_pallas

RNG = np.random.default_rng(42)


def test_ops_refuse_backends_other_than_cpu_and_tpu(monkeypatch):
    """Kernels are interpreted on the CPU backend and compiled on a TPU;
    on any other backend they raise instead of silently interpreting."""
    assert ops.interpret_mode() is True  # the test suite runs on CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops.digest(b"some bytes")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops.xor_reduce(np.zeros((2, 8), np.uint32))


# ---------------------------------------------------------------------------
# xor_parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [2, 3, 4, 8, 16])
@pytest.mark.parametrize("n", [1024, 4096])
def test_xor_reduce_sweep(k, n):
    x = RNG.integers(0, 2**32, size=(k, n), dtype=np.uint32)
    got = xor_reduce_pallas(jnp.asarray(x), interpret=True)
    want = ref.xor_reduce_ref(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [17, 1000, 5000])
def test_xor_reduce_unaligned_via_ops(n):
    x = RNG.integers(0, 2**32, size=(4, n), dtype=np.uint32)
    got = np.asarray(ops.xor_reduce(x))
    want = x[0] ^ x[1] ^ x[2] ^ x[3]
    np.testing.assert_array_equal(got, want)


def test_xor_pair():
    a = RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32)
    b = RNG.integers(0, 2**32, size=(2048,), dtype=np.uint32)
    got = xor_pair_pallas(jnp.asarray(a), jnp.asarray(b), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), a ^ b)


def test_xor_involution():
    """parity ^ shard_i recovers the reduce of the others (RAID property)."""
    x = RNG.integers(0, 2**32, size=(5, 2048), dtype=np.uint32)
    parity = np.asarray(ops.xor_reduce(x))
    for i in range(5):
        others = np.asarray(ops.xor_reduce(np.delete(x, i, axis=0)))
        np.testing.assert_array_equal(parity ^ x[i], others)


# ---------------------------------------------------------------------------
# checksum
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,chunk", [(8, 256), (16, 2048), (32, 512)])
def test_checksum_sweep(rows, chunk):
    x = RNG.integers(0, 2**32, size=(rows, chunk), dtype=np.uint32)
    got = checksum_pallas(jnp.asarray(x), block_rows=8, interpret=True)
    want = ref.checksum_ref(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_checksum_detects_reorder():
    x = RNG.integers(0, 2**32, size=(8, 256), dtype=np.uint32)
    y = x.copy()
    y[0, [3, 7]] = y[0, [7, 3]]  # swap two words: c1 equal, c2 must differ
    a = np.asarray(checksum_pallas(jnp.asarray(x), interpret=True))
    b = np.asarray(checksum_pallas(jnp.asarray(y), interpret=True))
    assert a[0, 0] == b[0, 0] and a[0, 1] != b[0, 1]


@pytest.mark.parametrize("rows,chunk", [(8, 256), (16, 2048), (32, 512)])
def test_blockhash_sweep(rows, chunk):
    x = RNG.integers(0, 2**32, size=(rows, chunk), dtype=np.uint32)
    got = blockhash_pallas(jnp.asarray(x), block_rows=8, interpret=True)
    want = ref.blockhash_ref(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_blockhash_avalanche_on_low_bit():
    """A single low-bit flip must change the chunk fingerprint — the plain
    Fletcher sums can cancel such flips, the mixed hash must not."""
    x = RNG.integers(0, 2**32, size=(8, 256), dtype=np.uint32)
    y = x.copy()
    y[3, 17] ^= 1
    a = np.asarray(blockhash_pallas(jnp.asarray(x), interpret=True))
    b = np.asarray(blockhash_pallas(jnp.asarray(y), interpret=True))
    assert (a[3] != b[3]).any()
    np.testing.assert_array_equal(np.delete(a, 3, 0), np.delete(b, 3, 0))


@given(st.binary(min_size=0, max_size=8192), st.integers(1, 64))
@settings(max_examples=25, deadline=None)
def test_block_fingerprints_locality(buf, chunk_words):
    """Flipping one byte changes exactly that chunk's fingerprint."""
    chunk_bytes = 4 * chunk_words
    fp = ops.block_fingerprints(buf, chunk_bytes=chunk_bytes)
    assert fp.shape[0] == -(-len(buf) // chunk_bytes)
    if not buf:
        return
    pos = len(buf) // 2
    mod = bytearray(buf)
    mod[pos] ^= 0xA5
    fp2 = ops.block_fingerprints(bytes(mod), chunk_bytes=chunk_bytes)
    changed = np.nonzero((fp != fp2).any(axis=1))[0]
    np.testing.assert_array_equal(changed, [pos // chunk_bytes])


@given(st.binary(min_size=0, max_size=4096))
@settings(max_examples=20, deadline=None)
def test_digest_deterministic(buf):
    assert ops.digest(buf) == ops.digest(buf)


@given(st.binary(min_size=16, max_size=2048), st.integers(0, 15))
@settings(max_examples=20, deadline=None)
def test_digest_detects_flip(buf, pos):
    mod = bytearray(buf)
    mod[pos] ^= 0x5A
    if bytes(mod) != buf:
        assert ops.digest(bytes(mod)) != ops.digest(buf)


#: bytes of one 64-row checksum tile: a digest views whole ones in place
TILE = 4 * 64 * CHUNK_WORDS

_checksum_rows = jax.jit(lambda x: checksum_pallas(x, interpret=True))


def _digest_padding_everything(raw: bytes) -> str:
    """The digest as computed before digests viewed their input: the whole
    buffer zero-padded to whole words, then to ``padded_rows`` rows, and
    checksummed as one tiling."""
    a = np.frombuffer(raw, np.uint8)
    words = np.concatenate([a, np.zeros(-a.size % 4, np.uint8)]) \
        .view(np.uint32)
    n_words = words.size
    if not n_words:
        return ops.fold_digest(np.zeros((0, 2), np.uint32), 0)
    rows = -(-n_words // CHUNK_WORDS)
    words = np.pad(words, (0, ops.padded_rows(rows) * CHUNK_WORDS - n_words))
    table = _checksum_rows(jnp.asarray(words.reshape(-1, CHUNK_WORDS)))
    return ops.fold_digest(np.asarray(table)[:rows], n_words)


def _typed(dtype):
    """The whole items of ``raw`` as a contiguous ndarray of ``dtype``."""
    def make(raw):
        size = np.dtype(dtype).itemsize
        return np.frombuffer(raw[:len(raw) - len(raw) % size], dtype)
    return make


DIGEST_INPUTS = {
    "bytes": bytes,
    "bytearray": bytearray,
    "memoryview": memoryview,
    "uint8": _typed(np.uint8),
    "float32": _typed(np.float32),
    "bfloat16": _typed(jnp.bfloat16),
    # every other byte of a doubled buffer: the same bytes, strided
    "strided": lambda raw: np.frombuffer(raw, np.uint8).repeat(2)[::2],
}


@pytest.mark.parametrize("kind", sorted(DIGEST_INPUTS))
@pytest.mark.parametrize("n", [0, 1, 3, 4, 5, 8191, 8192, 8193, TILE - 1,
                               TILE, TILE + 1, TILE + 3, 2 * TILE + 7,
                               3 * TILE])
def test_digest_matches_the_padded_whole_buffer(n, kind):
    """Digesting whole tiles in place and only the tail padded gives the
    digest of the whole zero-padded buffer, for every length and input
    type — the digests stored in shards, manifests and logs."""
    raw = np.random.default_rng(n).integers(0, 256, n, np.uint8).tobytes()
    buf = DIGEST_INPUTS[kind](raw)
    if isinstance(buf, np.ndarray):
        raw = buf.tobytes()
    assert ops.digest(buf) == _digest_padding_everything(raw)


def test_digest_is_pinned():
    """A fixed buffer of two tiles and three bytes digests as it always
    has, so no stored digest can drift."""
    n = 2 * TILE + 3
    buf = ((np.arange(n, dtype=np.uint64) * np.uint64(2654435761))
           >> np.uint64(13)).astype(np.uint8)
    assert ops.digest(buf.tobytes()) == "14f63d80d899438000040001"


@pytest.mark.parametrize("strided", [False, True])
def test_digest_views_whole_tiles_and_copies_the_tail(strided):
    """3 tiles and 5 bytes: one checksum program, the tiles handed over as
    a view, only the 5 tail bytes copied (the whole buffer once more when
    it is not contiguous)."""
    n = 3 * TILE + 5
    buf = np.random.default_rng(5).integers(0, 256, n, np.uint8)
    if strided:
        buf = buf.repeat(2)[::2]
    dispatches = ops.KERNEL_DISPATCHES["checksum"]
    host = dict(ops.DIGEST_HOST_BYTES)
    ops.digest(buf if strided else buf.tobytes())
    assert ops.KERNEL_DISPATCHES["checksum"] == dispatches + 1
    assert ops.DIGEST_HOST_BYTES["viewed"] - host["viewed"] == 3 * TILE
    assert ops.DIGEST_HOST_BYTES["copied"] - host["copied"] == \
        5 + (n if strided else 0)


# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,bs", [(32, 256), (64, 256), (32, 512)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_quantize_kernel_vs_ref(rows, bs, dtype):
    rng = np.random.default_rng((rows, bs, dtype().itemsize))
    x = (rng.standard_normal((rows, bs)) * 3).astype(dtype)
    q, s = quantize_pallas(jnp.asarray(x), interpret=True)
    qr, sr = ref.quantize_ref(jnp.asarray(x))
    # identical up to round-half-to-even ties at the f16->f32 boundary
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
    assert diff.max() <= 1
    assert (diff > 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    back = dequantize_pallas(q, s, interpret=True)
    br = ref.dequantize_ref(qr, sr)
    np.testing.assert_allclose(np.asarray(back), np.asarray(br), rtol=1e-6)


@given(st.integers(10, 5000), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_quantize_roundtrip_error_bound(n, seed):
    """Property: block-int8 quantization error <= scale/2 per element."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) * rng.uniform(0.1, 10)).astype(np.float32)
    q, s, n_out, shape = ops.quantize(x)
    back = ops.dequantize(q, s, n_out, shape)
    per_block_bound = np.repeat(s, 256)[:n] * 0.5 + 1e-7
    assert (np.abs(back - x) <= per_block_bound).all()


def test_quantize_preserves_shape_dtype_meta():
    x = RNG.standard_normal((7, 13, 3)).astype(np.float32)
    q, s, n, shape = ops.quantize(x)
    back = ops.dequantize(q, s, n, shape)
    assert back.shape == x.shape
    assert np.abs(back - x).max() < 0.5
