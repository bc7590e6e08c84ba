"""Erasure-code codec interface and base chunking logic.

Reference parity: ErasureCodeInterface
(/root/reference/src/erasure-code/ErasureCodeInterface.h:171-456) and the
ErasureCode base class's pad/align/chunk split + greedy minimum_to_decode
(/root/reference/src/erasure-code/ErasureCode.cc:44-61,75-110,112+).

API is kept 1:1 in spirit (init/get_chunk_count/get_chunk_size/
minimum_to_decode(_with_cost)/encode/decode/get_chunk_mapping/decode_concat)
but chunks are numpy byte arrays and errors are exceptions, not errno ints.
Chunk alignment is 128 bytes — the TPU lane width — instead of the
reference's SIMD_ALIGN=32.
"""

from __future__ import annotations

from abc import ABC, abstractmethod
from typing import Dict, List, Optional, Sequence, Set

import numpy as np

CHUNK_ALIGN = 128


class ErasureCodeError(Exception):
    pass


def have_jax() -> bool:
    """Shared capability probe for the TPU (jax) execution backend."""
    try:
        import jax  # noqa: F401
        return True
    except Exception:  # pragma: no cover
        return False


class ErasureCode(ABC):
    """Abstract codec; one instance per (pool) profile."""

    def __init__(self):
        self.profile: Dict[str, str] = {}

    # -- profile -------------------------------------------------------------
    def init(self, profile: Dict[str, str]) -> None:
        """Parse/validate the profile (reference init(), interface :205)."""
        self.profile = dict(profile)
        self._parse(self.profile)

    @abstractmethod
    def _parse(self, profile: Dict[str, str]) -> None:
        ...

    # -- geometry ------------------------------------------------------------
    @property
    @abstractmethod
    def k(self) -> int:
        ...

    @property
    @abstractmethod
    def m(self) -> int:
        ...

    def get_chunk_count(self) -> int:
        return self.k + self.m

    def get_data_chunk_count(self) -> int:
        return self.k

    def get_coding_chunk_count(self) -> int:
        return self.m

    def get_chunk_size(self, object_size: int) -> int:
        """ceil(object_size / k) rounded up to CHUNK_ALIGN
        (reference ErasureCode.cc pad+align semantics)."""
        per = (object_size + self.k - 1) // self.k
        return (per + CHUNK_ALIGN - 1) // CHUNK_ALIGN * CHUNK_ALIGN

    def get_chunk_mapping(self) -> List[int]:
        """Logical->physical chunk permutation; empty = identity
        (interface :391)."""
        return []

    # -- decode planning -----------------------------------------------------
    def minimum_to_decode(self, want_to_read: Set[int],
                          available: Set[int]) -> Set[int]:
        """Greedy: wanted chunks that are available, then fill to k
        (reference ErasureCode::minimum_to_decode)."""
        if want_to_read <= available:
            return set(want_to_read)
        if len(available) < self.k:
            raise ErasureCodeError(
                f"cannot decode: {len(available)} < k={self.k} available")
        minimum = set(want_to_read & available)
        for c in sorted(available):
            if len(minimum) >= self.k:
                break
            minimum.add(c)
        return minimum

    def minimum_to_decode_with_cost(self, want_to_read: Set[int],
                                    available: Dict[int, int]) -> Set[int]:
        """Cheapest decodable source set (interface :262; LRC overrides for
        locality).  Grows a cheapest-first prefix until minimum_to_decode
        accepts it, so non-MDS codecs that need specific chunks still work."""
        if want_to_read <= set(available):
            return set(want_to_read)
        cheap = sorted(available, key=lambda c: (available[c], c))
        last_err = None
        for n in range(1, len(cheap) + 1):
            try:
                return self.minimum_to_decode(want_to_read, set(cheap[:n]))
            except ErasureCodeError as e:
                last_err = e
        raise last_err if last_err is not None else ErasureCodeError(
            "no chunks available")

    # -- data path -----------------------------------------------------------
    def split_data(self, data: bytes) -> np.ndarray:
        """Pad+split an object into its [k, chunk] data chunks — the ONE
        place the stripe geometry is computed (reference
        ErasureCode::encode padding; also used by the OSD device batch
        queue so both encode paths pad identically).  A payload that
        is a whole stripe already (no padding) is VIEWED, not copied:
        the chunks are then read-only and share the payload's memory;
        no encode path writes to its data chunks."""
        chunk = self.get_chunk_size(len(data))
        flat = np.frombuffer(data, np.uint8)
        if flat.size == chunk * self.k:
            flat.flags.writeable = False    # a bytearray's view is not
            return flat.reshape(self.k, chunk)
        padded = np.zeros(chunk * self.k, np.uint8)
        padded[:flat.size] = flat
        return padded.reshape(self.k, chunk)

    def encode(self, want_to_encode: Set[int],
               data: bytes) -> Dict[int, np.ndarray]:
        """Pad+split into k chunks, compute parity, return wanted chunks
        (reference ErasureCode::encode -> encode_chunks)."""
        chunks = self.split_data(data)
        coded = self.encode_chunks(chunks)
        all_chunks = {i: chunks[i] for i in range(self.k)}
        all_chunks.update({self.k + i: coded[i] for i in range(self.m)})
        return {i: all_chunks[i] for i in want_to_encode}

    @abstractmethod
    def encode_chunks(self, data_chunks: np.ndarray) -> np.ndarray:
        """[k, L] data -> [m, L] parity."""
        ...

    def decode(self, want_to_read: Set[int],
               chunks: Dict[int, np.ndarray]) -> Dict[int, np.ndarray]:
        """Reconstruct wanted chunk ids from any >=k available chunks
        (reference ErasureCode::decode / plugin decode_chunks)."""
        have = {i for i in chunks}
        missing_wanted = sorted(set(want_to_read) - have)
        out = {i: np.asarray(chunks[i])
               for i in want_to_read if i in chunks}
        if not missing_wanted:
            return out
        # note: no >=k precondition here — sparse codes (shec) and layered
        # codes (lrc) can repair locally from fewer than k chunks; each
        # decode_chunks raises ErasureCodeError when truly undecodable.
        out.update(self.decode_chunks(missing_wanted, chunks))
        return out

    @abstractmethod
    def decode_chunks(self, want: Sequence[int],
                      chunks: Dict[int, np.ndarray]) -> Dict[int, np.ndarray]:
        ...

    def decode_concat(self, chunks: Dict[int, np.ndarray]) -> bytes:
        """Reconstruct and concatenate the data chunks (interface :430)."""
        want = set(range(self.k))
        decoded = self.decode(want, chunks)
        return b"".join(decoded[i].tobytes() for i in range(self.k))

    # -- placement hook ------------------------------------------------------
    def create_rule(self, crush_map, name: str,
                    failure_domain: str = "host") -> int:
        """Reference create_ruleset (interface :181): an indep rule choosing
        k+m distinct failure domains for positionally-stable EC placement."""
        from ceph_tpu.crush.builder import make_erasure_rule
        return make_erasure_rule(crush_map, name, self.get_chunk_count(),
                                 failure_domain)
