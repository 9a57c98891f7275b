"""Property-based round-trips (hypothesis): shard serialize/read across
encodings and dtypes, delta encode/overlay under randomized dirty masks,
and the durable stream catalog container — byte-identical or an error,
never silent corruption."""
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis "
    "(pip install -r requirements-dev.txt)")
from hypothesis import example, given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import delta as dlt  # noqa: E402
from repro.core import format as fmt  # noqa: E402

DTYPES = [np.float32, np.float64, np.int32, np.uint8, np.int8]


def _array(data, dtype, n):
    if np.dtype(dtype).kind == "f":
        vals = data.draw(st.lists(
            st.floats(-1e6, 1e6, allow_nan=False, width=32),
            min_size=n, max_size=n))
    else:
        info = np.iinfo(dtype)
        vals = data.draw(st.lists(
            st.integers(int(info.min), int(info.max)),
            min_size=n, max_size=n))
    return np.asarray(vals, dtype=dtype)


@settings(max_examples=25, deadline=None)
@given(data=st.data(),
       dtype=st.sampled_from(DTYPES),
       n=st.integers(0, 300),
       encoding=st.sampled_from(["raw", "zlib"]))
def test_shard_roundtrip_lossless(data, dtype, n, encoding):
    arr = _array(data, dtype, n)
    blob = fmt.serialize_shard([fmt.Region("r", arr)], {"v": 1},
                               encoding=encoding)
    reader = fmt.ShardReader(blob)
    out = reader.read("r")
    assert out.dtype == arr.dtype and out.shape == arr.shape
    assert out.tobytes() == arr.tobytes()
    assert reader.verify("r")
    assert reader.meta == {"v": 1}


#: past one quantize kernel tile (1024 rows of 256 values): a 3-step grid
_Q8_MULTI_TILE = 2 * 1024 * 256 + 77


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**32 - 1),
       n=st.integers(1024, 4096) | st.just(_Q8_MULTI_TILE))
@example(seed=0, n=_Q8_MULTI_TILE)
def test_shard_roundtrip_q8_lossy_bounded(seed, n):
    """q8 is lossy: round-trip must stay within one quantization step of
    the block absmax.  The floats come from NumPy with a drawn seed:
    thousands of hypothesis-drawn floats per example trip its
    large-base-example health check."""
    rng = np.random.default_rng(seed)
    arr = (rng.uniform(-1e6, 1e6, n) * 10.0 ** rng.uniform(-6, 0)
           ).astype(np.float32)
    blob = fmt.serialize_shard([fmt.Region("r", arr)], {}, encoding="q8")
    out = fmt.ShardReader(blob).read("r")
    assert out.shape == arr.shape
    step = np.abs(arr).max() / 127.0 + 1e-6
    assert np.abs(out - arr).max() <= step * 1.01


@settings(max_examples=25, deadline=None)
@given(data=st.data(),
       dtype=st.sampled_from(DTYPES),
       n=st.integers(0, 2000),
       chunk_words=st.integers(1, 64),
       n_dirty=st.integers(0, 40))
def test_delta_overlay_randomized_dirty_masks(data, dtype, n, chunk_words,
                                              n_dirty):
    """overlay(base, diff(new, base)) == new, byte-identical, for random
    dirty masks, any dtype, empty and non-multiple-of-chunk regions."""
    chunk_bytes = chunk_words * 4
    base = _array(data, dtype, n)
    new = base.copy()
    if n > 0:
        idx = data.draw(st.lists(st.integers(0, n - 1), min_size=0,
                                 max_size=min(n_dirty, n), unique=True))
        for i in idx:
            flipped = np.frombuffer(
                (~np.frombuffer(new[i:i + 1].tobytes(), np.uint8)).tobytes(),
                dtype=dtype)[0]
            new[i] = flipped
    _, fp0 = dlt.make_patch(base, None, chunk_bytes=chunk_bytes)
    patch, _ = dlt.make_patch(new, fp0, chunk_bytes=chunk_bytes,
                              base_version=1)
    decoded = dlt.decode_patch(dlt.encode_patch(patch))
    out = dlt.overlay(base, decoded)
    assert out.tobytes() == new.tobytes()
    assert out.dtype == new.dtype and out.shape == new.shape


@settings(max_examples=15, deadline=None)
@given(data=st.data(), n=st.integers(1, 500),
       chunk_words=st.integers(1, 32))
def test_delta_region_through_shard_container(data, n, chunk_words):
    """The "delta" region encoding round-trips through the shard container
    next to raw regions."""
    chunk_bytes = chunk_words * 4
    base = _array(data, np.float32, n)
    new = base.copy()
    new[data.draw(st.integers(0, n - 1))] += 1.0
    _, fp0 = dlt.make_patch(base, None, chunk_bytes=chunk_bytes)
    patch, _ = dlt.make_patch(new, fp0, chunk_bytes=chunk_bytes,
                              base_version=7)
    other = _array(data, np.int32, 5)
    blob = fmt.serialize_shard(
        [fmt.Region("w", new, patch=patch), fmt.Region("o", other)],
        {"delta": {"kind": "delta", "parent": 7}})
    reader = fmt.ShardReader(blob)
    assert reader.delta_regions() == ["w"]
    assert reader.entry("w")["base_version"] == 7
    assert reader.read("w", base=base).tobytes() == new.tobytes()
    assert reader.read("o").tobytes() == other.tobytes()
    assert reader.read_patch("w").base_version == 7


_CAT_RECORD = st.fixed_dictionaries({
    "kind": st.sampled_from(["full", "delta"]),
    "parent": st.none() | st.integers(0, 10**6),
    "sealed": st.booleans(),
    "location": st.sampled_from(["direct", "segment", "pack"]),
    "pack": st.none() | st.text(min_size=1, max_size=24),
    "entries": st.none() | st.lists(st.text(max_size=16), max_size=6),
    "levels": st.lists(st.sampled_from(["L1", "L2", "L3"]), unique=True),
    "stamp": st.text(max_size=16),
})


@settings(max_examples=30, deadline=None)
@given(versions=st.dictionaries(st.integers(0, 10**8), _CAT_RECORD,
                                max_size=8),
       tombstones=st.lists(st.tuples(st.integers(0, 10**8),
                                     st.text(max_size=16)), max_size=6),
       gen=st.integers(1, 10**9),
       name=st.text(min_size=1, max_size=24))
def test_catalog_roundtrip_property(versions, tombstones, gen, name):
    """Durable stream catalog: encode/decode is the identity (modulo the
    canonical sorted form of entry sets and int version keys)."""
    blob = fmt.encode_catalog(name, versions, tombstones, gen=gen,
                              writer="w")
    dec = fmt.decode_catalog(blob)
    assert dec["name"] == name and dec["gen"] == gen
    assert set(dec["versions"]) == set(versions)
    for v, rec in versions.items():
        want = dict(rec)
        if want["entries"] is not None:
            want["entries"] = sorted(want["entries"])
        assert dec["versions"][v] == want
    assert dec["tombstones"] == [[v, s] for v, s in tombstones]


@settings(max_examples=40, deadline=None)
@given(versions=st.dictionaries(st.integers(0, 10**8), _CAT_RECORD,
                                min_size=1, max_size=6),
       flip=st.integers(0, 10**6),
       cut=st.integers(1, 10**6))
def test_catalog_corruption_never_silent(versions, flip, cut):
    """Flipping any byte — or truncating at any point — of an encoded
    catalog raises IOError at decode; a torn catalog can never silently
    drop versions from GC's or restart's view."""
    blob = fmt.encode_catalog("s", versions, [[0, "t"]], gen=3, writer="w")
    flipped = bytearray(blob)
    flipped[flip % len(blob)] ^= 0x01
    with pytest.raises(IOError):
        fmt.decode_catalog(bytes(flipped))
    with pytest.raises(IOError):
        fmt.decode_catalog(blob[:cut % len(blob)])


@settings(max_examples=15, deadline=None)
@given(data=st.data(), n=st.integers(4, 400), flip=st.integers(0, 10**6))
def test_delta_blob_corruption_never_silent(data, n, flip):
    """Flipping any byte of an encoded patch either raises on decode/overlay
    or still yields the correct array (flips in dead padding don't exist:
    every byte is header, table or chunk data)."""
    base = _array(data, np.float32, n)
    new = base.copy()
    new[n // 2] += 1.0
    _, fp0 = dlt.make_patch(base, None, chunk_bytes=16)
    patch, _ = dlt.make_patch(new, fp0, chunk_bytes=16, base_version=1)
    blob = bytearray(dlt.encode_patch(patch))
    blob[flip % len(blob)] ^= 0x01
    try:
        out = dlt.overlay(base, dlt.decode_patch(bytes(blob)))
    except Exception:
        return  # detected — good
    assert out.tobytes() == new.tobytes()
