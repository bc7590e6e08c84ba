"""What a GF(2^8) matrix apply has to move, whatever implements it.

out[r, L] = mat[r, k] @ chunks[k, L] reads k * L bytes and writes
r * L, and nothing of it can be reused: the least HBM traffic of one
request is (k + r) * L bytes.  The arithmetic is trivial beside it
(the kernel is bound by memory), so the least time the chip could take
is those bytes over the peak HBM rate.  The bytes are the REQUESTS' own,
unpadded: padding to a lane bucket is the implementation's cost, not
the algorithm's, so a kernel that pads less scores higher."""

from __future__ import annotations


def ec_apply_bytes(k: int, r: int, lanes: int) -> int:
    """Least bytes moved for requests of `lanes` unpadded lanes in all."""
    return (k + r) * lanes


def roofline_share(min_bytes: float, kernel_seconds: float,
                   hbm_bytes_per_s: float) -> float:
    """Percent of the memory roofline: least time over measured time."""
    if kernel_seconds <= 0:
        raise ValueError("kernel time must be above 0")
    return 100.0 * (min_bytes / hbm_bytes_per_s) / kernel_seconds
