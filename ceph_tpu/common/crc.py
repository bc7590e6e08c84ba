"""crc32c (Castagnoli) with native dispatch.

Reference parity: common/crc32c.h — the digest used for chunk/object
integrity (ECBackend hash info, scrub compares).  Uses the native
kernel (native/src/native.cc) when built: the CPU's CRC32C instruction
where the build machine has it, slicing-by-8 otherwise (crc32c_impl()
says which).  The byte-at-a-time table below keeps pure-python
environments working with identical digests.
"""

from __future__ import annotations

from ceph_tpu import native

_TABLE = None


def _table():
    global _TABLE
    if _TABLE is None:
        t = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (0x82F63B78 ^ (c >> 1)) if c & 1 else c >> 1
            t.append(c)
        _TABLE = t
    return _TABLE


def crc32c_impl() -> str:
    """The path crc32c takes in this process."""
    return native.crc32c_impl() if native.available() else "python"


def crc32c(data, crc: int = 0) -> int:
    if not isinstance(data, (bytes, bytearray, memoryview)):
        data = bytes(data)      # a lane's lazy ExtentRef materialises
    if native.available():
        return native.crc32c(data, crc)
    return crc32c_python(data, crc)


def crc32c_many(blobs) -> list:
    """crc32c of each of `blobs` (`bytes` objects).  On the native
    kernel one call digests them all, so the GIL changes hands once."""
    if native.available():
        return native.crc32c_many(blobs)
    return [crc32c_python(b) for b in blobs]


def crc32c_python(data, crc: int = 0) -> int:
    t = _table()
    c = crc ^ 0xFFFFFFFF
    for b in data:
        c = t[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF
