"""Native host runtime tests: LZ4 codec, row<->column conversion, host
pool (SURVEY §2.9 native seam)."""

import random

import numpy as np
import pytest

from spark_rapids_tpu.native import (HostMemoryPool, columns_to_rows,
                                     lz4_compress, lz4_decompress,
                                     native_available, rows_to_columns)


def test_native_builds():
    assert native_available()


@pytest.mark.parametrize("payload", [
    b"", b"a", b"hello world hello world hello world",
    b"abc" * 1000, bytes(range(256)) * 64,
    # incompressible, but the same bytes (and test id) in every process
    random.Random(4096).randbytes(4096),
    b"\x00" * 10000,
])
def test_lz4_roundtrip(payload):
    comp = lz4_compress(payload)
    back = lz4_decompress(comp, len(payload))
    assert back == payload


def test_lz4_actually_compresses():
    data = b"the quick brown fox " * 500
    comp = lz4_compress(data)
    assert len(comp) < len(data) // 4


def test_lz4_rejects_corrupt():
    data = b"abcabcabc" * 100
    comp = bytearray(lz4_compress(data))
    comp[5] ^= 0xFF
    with pytest.raises(RuntimeError):
        lz4_decompress(bytes(comp), len(data))


def test_rows_columns_roundtrip():
    rng = np.random.default_rng(3)
    n = 500
    cols = [rng.integers(-1000, 1000, n).astype(np.int64),
            rng.uniform(-1, 1, n).astype(np.float64),
            rng.integers(0, 100, n).astype(np.int32),
            rng.integers(0, 2, n).astype(np.int8)]
    valids = [rng.random(n) > 0.2 for _ in cols]
    sizes = [8, 8, 4, 1]
    rows, stride, offsets = columns_to_rows(cols, valids, sizes)
    assert stride % 8 == 0
    out, out_valid = rows_to_columns(rows, stride, n, sizes, offsets,
                                     [np.int64, np.float64, np.int32,
                                      np.int8])
    for c, v, oc, ov in zip(cols, valids, out, out_valid):
        assert (ov == v).all()
        assert (oc[v] == c[v]).all()
        assert (oc[~v] == 0).all()  # nulls zeroed


def test_host_pool():
    pool = HostMemoryPool(1 << 20)
    a = pool.alloc(1000)
    b = pool.alloc(2000)
    assert a and b and a != b
    stats = pool.stats()
    assert stats["alloc_count"] == 2
    assert stats["in_use"] >= 3000
    pool.free(a)
    # exhausted pool returns None (spill-and-retry signal), not a crash
    big = pool.alloc(2 << 20)
    assert big is None
    assert pool.stats()["fail_count"] == 1
    # coalescing: freeing everything lets a full-size alloc succeed
    pool.free(b)
    c = pool.alloc((1 << 20) - 4096)
    assert c is not None
    pool.free(c)
    with pytest.raises(ValueError):
        pool.free(12345)
    pool.close()


def test_lz4_shuffle_codec_end_to_end():
    from spark_rapids_tpu.columnar.vector import (batch_from_pydict,
                                                  batch_to_pydict)
    from spark_rapids_tpu.parallel.serializer import (deserialize_batch,
                                                      serialize_batch)
    b = batch_from_pydict({"v": list(range(100)),
                           "s": [f"row{i % 7}" for i in range(100)]})
    data = serialize_batch(b, compress=True, codec="lz4")
    plain = serialize_batch(b, compress=False)
    assert len(data) < len(plain)
    back = deserialize_batch(data)
    assert batch_to_pydict(back) == batch_to_pydict(b)


def test_host_heap_settings_are_taken_by_glibc():
    """The scan's host buffers stay in the heap: glibc takes the mmap
    threshold, the trim threshold and the pad, and a second call is as
    good as the first."""
    import ctypes

    from spark_rapids_tpu import native
    libc = ctypes.CDLL(None)
    if not hasattr(libc, "mallopt"):
        pytest.skip("this libc has no mallopt")
    assert native._retain_host_heap() is True
    assert native._retain_host_heap() is True


def test_host_heap_is_left_alone_without_mallopt(monkeypatch):
    """A libc without ``mallopt`` (musl, macOS) loads the library all the
    same."""
    import ctypes

    from spark_rapids_tpu import native

    class NoMallopt:
        def __getattr__(self, name):
            raise AttributeError(name)
    real = ctypes.CDLL
    monkeypatch.setattr(ctypes, "CDLL",
                        lambda name, *a, **k: NoMallopt() if name is None
                        else real(name, *a, **k))
    assert native._retain_host_heap() is False
    native.load()
