"""The plain reference for objects that are NOT a whole stripe: what the
k + m shards of a whole-object write of ANY length hold.

`benchmark.reference.shards` refuses a length that does not split into
k equal chunks; a 1,000-byte record on k=2 does split (two chunks of
500), and still that is not what an erasure-coded pool stores.  The
geometry, written down plainly and independent of the program
(ceph_tpu/ec/interface.py is never imported here):

    chunk = ceil(len / k), rounded up to a multiple of CHUNK_ALIGN (128)
    the object is laid into k * chunk bytes, the tail zero-filled
    shard i < k stores bytes [i * chunk, (i + 1) * chunk) of that
    shard k + j stores parity row j of the k chunks

so 1,000 bytes over k=2 are two chunks of 512 with 24 bytes of zeros at
the end of the second, and the parity is over the padded chunks.  The
object's LENGTH is not in the shards: a read returns the first `len`
bytes of the k data chunks joined.

The field and the generator are `benchmark.reference`'s; nothing of
ceph_tpu is imported."""

from __future__ import annotations

from typing import List

import numpy as np

from benchmark import reference

CHUNK_ALIGN = 128


def chunk_size(length: int, k: int) -> int:
    """Bytes per shard of an object of `length` bytes."""
    per = -(-length // k)
    return -(-per // CHUNK_ALIGN) * CHUNK_ALIGN


def shards(data: bytes, k: int, m: int) -> List[np.ndarray]:
    """The k + m shard streams a whole-object write of `data` stores."""
    chunk = chunk_size(len(data), k)
    padded = np.zeros(k * chunk, np.uint8)
    padded[:len(data)] = np.frombuffer(data, np.uint8)
    chunks = padded.reshape(k, chunk)
    parity = reference.apply(reference.generator(k, m)[k:], chunks)
    return [chunks[i] for i in range(k)] + [parity[j] for j in range(m)]

