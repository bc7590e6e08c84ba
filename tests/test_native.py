"""Native library tests: crc32c check vectors and the agreement of its
three implementations, rjenkins parity with the python hash, GF(2^8)
apply parity with gf256.host_apply."""

import numpy as np
import pytest

from ceph_tpu import native
from ceph_tpu.common.crc import crc32c, crc32c_python

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native lib unavailable")


def test_crc32c_check_vectors():
    # standard castagnoli check value
    assert native.crc32c(b"123456789") == 0xE3069283
    assert native.crc32c(b"") == 0
    # incremental == one-shot
    whole = native.crc32c(b"hello world")
    part = native.crc32c(b" world", native.crc32c(b"hello"))
    assert whole == part
    # unaligned head loop: crc of an offset numpy view must equal crc of a
    # fresh (aligned) copy of the same bytes
    raw = np.frombuffer(bytes(range(256)) * 3, np.uint8)
    for off in range(1, 9):
        view = raw[off:]
        aligned = view.copy()
        assert native.crc32c(view.tobytes()) == \
            native.crc32c(aligned.tobytes())
        # drive the C pointer-alignment path directly via an offset view
        import ctypes
        lib = native._load()
        u8p = ctypes.POINTER(ctypes.c_uint8)
        got = lib.ceph_crc32c(0, view.ctypes.data_as(u8p), view.size)
        assert got == native.crc32c(aligned.tobytes())


# ceph_crc32c's interleave constants (native.cc): three streams of a
# long block, then of a short block, then one stream
CRC_LONG, CRC_SHORT = 8192, 256
CRC_DATA = np.random.default_rng(25).integers(
    0, 256, (1 << 20) + 5 + 16, dtype=np.uint8)
CRC_LENGTHS = sorted(
    set(range(71)) | {255, 256, 257, 1 << 20, (1 << 20) + 5}
    | {n + d for n in (CRC_SHORT, 3 * CRC_SHORT, CRC_LONG, 3 * CRC_LONG)
       for d in (-1, 0, 1)})


def _view(length: int, off: int = 0) -> np.ndarray:
    """`length` bytes of CRC_DATA starting `off` bytes past an 8-byte
    boundary of the address space (what the C head loop looks at)."""
    start = (-CRC_DATA.ctypes.data) % 8 + off
    v = CRC_DATA[start:start + length]
    assert v.size == length
    assert not length or v.ctypes.data % 8 == off % 8
    return v


@pytest.mark.parametrize("length", CRC_LENGTHS)
def test_crc32c_three_paths_agree(length):
    v = _view(length)
    want = crc32c_python(v.tobytes())
    assert native.crc32c_table(v) == want
    assert native.crc32c(v) == want
    assert crc32c(memoryview(v)) == want


@pytest.mark.parametrize("lengths", [
    (), (0,), (1, 0, 9), (1 << 20,) * 6,
    (3 * CRC_LONG + CRC_SHORT + 1, 7, 3 * CRC_SHORT + 3, 70)],
    ids=["none", "empty", "short", "six_shards", "mixed"])
def test_crc32c_many_is_each_buffers_own_digest(lengths):
    """One call for several `bytes` (a full EC write's six shards): each
    digest is that buffer's own, NUL bytes and empty buffers included,
    and the python fallback of common.crc agrees."""
    from ceph_tpu.common import crc as crc_mod
    blobs = [bytes(_view(n, off=i % 8)) for i, n in enumerate(lengths)]
    want = [native.crc32c_table(b) for b in blobs]
    assert native.crc32c_many(blobs) == want
    assert crc_mod.crc32c_many(blobs) == want
    assert [crc32c_python(b) for b in blobs[:3]] == want[:3]


@pytest.mark.parametrize("off", range(9))
@pytest.mark.parametrize(
    "length", [0, 7, 70, 3 * CRC_SHORT + 3, 3 * CRC_LONG + CRC_SHORT + 1])
def test_crc32c_unaligned_start(off, length):
    v = _view(length, off)
    want = native.crc32c_table(v.copy())
    assert native.crc32c(v) == want
    assert native.crc32c_table(v) == want


@pytest.mark.parametrize("cuts", [
    (0,), (1,), (5, 6, 7), (63, 64, 65),
    (CRC_SHORT,), (3 * CRC_SHORT - 1,), (3 * CRC_SHORT + 1,),
    (100, 2 * CRC_SHORT + 9),                  # inside stream 0 and 2
    (CRC_LONG,), (2 * CRC_LONG,), (3 * CRC_LONG,),   # on stream edges
    (CRC_LONG - 3, CRC_LONG + 3),              # across an edge
    (CRC_LONG // 2, CRC_LONG + 11, 2 * CRC_LONG + 4097),
    (3 * CRC_LONG + 5, 6 * CRC_LONG + CRC_SHORT + 1),
    (12345, 12346, 50000, 50001, 77777),
], ids=lambda c: "-".join(map(str, c)))
def test_crc32c_seed_chaining(cuts):
    """crc32c(b, crc32c(a)) == crc32c(a + b) wherever a ends: inside a
    stream, on a stream boundary, across one."""
    v = _view(6 * CRC_LONG + 3 * CRC_SHORT + 101, off=3)
    want = native.crc32c_table(v)
    assert native.crc32c(v) == want
    edges = [0, *cuts, v.size]
    crc = crc_t = 0
    for a, b in zip(edges, edges[1:]):
        crc = native.crc32c(v[a:b], crc)
        crc_t = native.crc32c_table(v[a:b], crc_t)
    assert crc == want and crc_t == want


@pytest.mark.parametrize("data,want", [
    (bytes(32), 0x8A9136AA),
    (b"\xff" * 32, 0x62A8AB43),
    (bytes(range(32)), 0x46DD794E),
    (bytes(range(31, -1, -1)), 0x113FDB5C),
], ids=["zeros", "ones", "ascending", "descending"])
def test_crc32c_iscsi_vectors(data, want):
    # RFC 3720 B.4
    assert native.crc32c(data) == want
    assert native.crc32c_table(data) == want
    assert crc32c_python(data) == want


def test_crc32c_impl_follows_the_cpu():
    """The build decides: a CPU with SSE4.2 gets the instruction."""
    has = "sse4_2" in native._cpu_flags().split()   # /proc/cpuinfo's line
    impl = native.crc32c_impl()
    assert (impl != "table") if has else (impl == "table")


def test_crc32c_accepts_what_callers_hand_it():
    class Lazy:                     # osd/extents.ExtentRef's shape
        def __bytes__(self):
            return b"123456789"
    for data in (b"123456789", bytearray(b"123456789"),
                 memoryview(b"x123456789")[1:], Lazy(),
                 np.frombuffer(b"123456789", np.uint8)):
        assert crc32c(data) == 0xE3069283


def test_rjenkins_matches_python():
    from ceph_tpu.crush.hashfn import hash32_3
    rng = np.random.default_rng(0)
    for _ in range(100):
        a, b, c = (int(x) for x in rng.integers(0, 2**32, 3))
        assert native.rjenkins3(a, b, c) == hash32_3(a, b, c)


def test_rjenkins_batch_matches_scalar():
    from ceph_tpu.crush.hashfn import hash32_3
    rng = np.random.default_rng(3)
    a = rng.integers(0, 2**32, 64, dtype=np.uint32)
    out = native.rjenkins3_batch(a, 7, 123456)
    for i in range(a.size):
        assert out[i] == hash32_3(int(a[i]), 7, 123456)


def test_gf_matrix_apply_matches_host():
    from ceph_tpu.ec import gf256
    rng = np.random.default_rng(1)
    for (r, k, L) in [(1, 2, 64), (4, 8, 1000), (2, 3, 7)]:
        mat = rng.integers(0, 256, (r, k)).astype(np.uint8)
        chunks = rng.integers(0, 256, (k, L)).astype(np.uint8)
        assert np.array_equal(native.gf_matrix_apply(mat, chunks),
                              gf256.host_apply(mat, chunks))


def test_gf_simd_matches_scalar():
    # GFNI/AVX-512 kernel (when the host has it) vs the table sweep —
    # including the non-multiple-of-64 scalar tail path
    from ceph_tpu.ec import gf256
    if not native.gf_simd_available():
        import pytest
        pytest.skip("no GFNI/AVX-512 on this host")
    rng = np.random.default_rng(2)
    for (r, k, L) in [(4, 8, 1 << 16), (2, 8, 100001), (3, 5, 63)]:
        mat = rng.integers(0, 256, (r, k)).astype(np.uint8)
        chunks = rng.integers(0, 256, (k, L)).astype(np.uint8)
        got = native.gf_matrix_apply(mat, chunks)
        want = native.gf_matrix_apply(mat, chunks, force_scalar=True)
        assert np.array_equal(got, want), (r, k, L)
        assert np.array_equal(got, gf256.host_apply(mat, chunks))


def test_region_xor():
    rng = np.random.default_rng(2)
    a = rng.integers(0, 256, 1000).astype(np.uint8)
    b = rng.integers(0, 256, 1000).astype(np.uint8)
    assert np.array_equal(native.region_xor(a, b), a ^ b)
