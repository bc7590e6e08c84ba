"""The plain reference: what the cell's guarantees say the cluster must
hold and return, worked out without the program.

It imports nothing of ceph_tpu.  GF(2^8) is the conventional field of
polynomial 0x11d (ISA-L, jerasure w=8); the generator is ISA-L's
`gf_gen_rs_matrix` shape for technique reed_sol_van: Vandermonde
V[i, j] = i**j, normalised so that the top k rows are the identity.
An object of k * L bytes splits into k contiguous chunks of L bytes;
shard i < k stores chunk i and shard k + j stores parity row j."""

from __future__ import annotations

from functools import lru_cache
from typing import List, Sequence

import numpy as np

POLY = 0x11D


@lru_cache(maxsize=1)
def mul_table() -> np.ndarray:
    """T[a, b] = a * b in GF(2^8), by shift-and-reduce (no log tables)."""
    t = np.zeros((256, 256), np.uint8)
    b = np.arange(256, dtype=np.uint16)
    for a in range(256):
        acc = np.zeros(256, np.uint16)
        x, bb = a, b.copy()
        while x:
            if x & 1:
                acc ^= bb
            bb <<= 1
            bb = np.where(bb & 0x100, bb ^ POLY, bb)
            x >>= 1
        t[a] = acc.astype(np.uint8)
    return t


def gf_mul(a: int, b: int) -> int:
    return int(mul_table()[a, b])


def gf_pow(a: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = gf_mul(out, a)
    return out


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("inverse of 0 in GF(2^8)")
    return int(np.nonzero(mul_table()[a] == 1)[0][0])


def mat_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    t = mul_table()
    out = np.zeros((a.shape[0], b.shape[1]), np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for x in range(a.shape[1]):
                acc ^= int(t[a[i, x], b[x, j]])
            out[i, j] = acc
    return out


def mat_inv(a: np.ndarray) -> np.ndarray:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = a.shape[0]
    t = mul_table()
    m = np.concatenate([a.astype(np.uint8), np.eye(n, dtype=np.uint8)], 1)
    for col in range(n):
        piv = next((r for r in range(col, n) if m[r, col]), None)
        if piv is None:
            raise ValueError("singular matrix over GF(2^8)")
        m[[col, piv]] = m[[piv, col]]
        m[col] = t[gf_inv(int(m[col, col]))][m[col]]
        for r in range(n):
            if r != col and m[r, col]:
                m[r] ^= t[int(m[r, col])][m[col]]
    return m[:, n:]


@lru_cache(maxsize=8)
def _generator(k: int, m: int) -> bytes:
    v = np.zeros((k + m, k), np.uint8)
    for i in range(k + m):
        for j in range(k):
            v[i, j] = gf_pow(i, j) if i else (1 if j == 0 else 0)
    return mat_mul(v, mat_inv(v[:k])).tobytes()


def generator(k: int, m: int) -> np.ndarray:
    """Systematic RS-Vandermonde generator, [(k + m), k]."""
    return np.frombuffer(_generator(k, m), np.uint8).reshape(k + m, k)


def decode_matrix(k: int, m: int, present: Sequence[int],
                  want: Sequence[int]) -> np.ndarray:
    """Rows that rebuild chunk ids `want` from the first k `present`."""
    gen = generator(k, m)
    inv = mat_inv(gen[list(present)[:k]])
    return mat_mul(gen[list(want)], inv)


def apply(mat: np.ndarray, chunks: np.ndarray) -> np.ndarray:
    """out[r] = XOR_j mat[r, j] * chunks[j], by table lookup."""
    t = mul_table()
    out = np.zeros((mat.shape[0], chunks.shape[1]), np.uint8)
    for r in range(mat.shape[0]):
        for j in range(mat.shape[1]):
            c = int(mat[r, j])
            if c:
                out[r] ^= t[c][chunks[j]]
    return out


def shards(data: bytes, k: int, m: int) -> List[np.ndarray]:
    """The k + m shard streams a whole-object write of `data` stores."""
    if len(data) % k:
        raise ValueError(f"object of {len(data)} bytes does not split "
                         f"into {k} equal chunks")
    chunks = np.frombuffer(data, np.uint8).reshape(k, -1)
    parity = apply(generator(k, m)[k:], chunks)
    return [chunks[i] for i in range(k)] + [parity[j] for j in range(m)]


def payloads(seed: int, count: int, size: int) -> List[bytes]:
    """`count` distinct payloads of `size` bytes, made from the seed in
    one bulk draw (every byte random: nothing compresses or dedups)."""
    rng = np.random.default_rng([int(seed), 0x9A710AD5])
    blob = rng.integers(0, 256, count * size, dtype=np.uint8).tobytes()
    return [blob[i * size:(i + 1) * size] for i in range(count)]
