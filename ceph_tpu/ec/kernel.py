"""TPU erasure-code kernel: GF(2^8) matrix apply as a mod-2 MXU matmul.

Replaces the reference's x86 GF(2^8) SIMD kernels
(/root/reference/src/erasure-code/isa/isa-l/erasure_code/*.asm.s, dispatched
from ec_highlevel_func.c / ErasureCodeIsa.cc:144-155) with a TPU-native
lowering:

  * a GF(2^8) constant multiply is linear over GF(2), so the (r x k) code
    matrix expands to an (8r x 8k) 0/1 bit-matrix B (gf256.expand_to_bitmatrix)
  * data chunks [k, L] bytes are unpacked to bit-planes x [8k, L]
  * y = (B @ x) mod 2 — an int8 matmul with int32 accumulation, which XLA
    places on the MXU; the mod-2 and byte re-pack fuse into the epilogue
  * output planes repack to [r, L] bytes

The matmul's M/K dims are small (8r x 8k, e.g. 32x64 for k=8,m=4) while L is
the full chunk length, so the op is HBM-bandwidth-bound — the right regime
for a storage codec.  Everything is shape-static and jit-cached per
(8r, 8k, L).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ceph_tpu.common import devstats


def _unpack_bits(data: jnp.ndarray) -> jnp.ndarray:
    """[k, L] uint8 -> [8k, L] int8 bit-planes, plane order (chunk, bit)."""
    k, L = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[:, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(k * 8, L).astype(jnp.int8)


def _pack_bits(planes: jnp.ndarray) -> jnp.ndarray:
    """[8r, L] {0,1} uint8 -> [r, L] uint8 bytes."""
    r8, L = planes.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    b = planes.reshape(r8 // 8, 8, L) << shifts[None, :, None]
    return jnp.bitwise_or.reduce(b, axis=1)


@partial(jax.jit, static_argnames=())
def _apply_bitmatrix(bitmat: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """y[r, L] = GF(2^8) matrix apply, computed as mod-2 MXU matmul."""
    x = _unpack_bits(data)                              # [8k, L] int8
    acc = jax.lax.dot_general(
        bitmat, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)               # [8r, L] int32
    planes = (acc & 1).astype(jnp.uint8)
    return _pack_bits(planes)


# ------------------------------------------------------------ pallas path
#
# The XLA lowering above materializes the [8k, L] bit-plane operand (and
# the [8r, L] int32 accumulator) in HBM — ~8x the stripe's data traffic.
# The pallas kernel fuses unpack -> matmul -> mod2 -> pack inside VMEM:
# per L-tile, HBM sees only the [k, T] byte read and [r, T] byte write.
#
# The kernel is PARAMETERIZED (tile length, plane layout, pack engine);
# autotune() below times the variants on the live chip:
#   * layout="cb": planes in (chunk, bit) order — B used as-is, but the
#     stack(axis=1).reshape interleave is a relayout-heavy shuffle
#   * layout="bc": planes in (bit, chunk) order — a plain concatenation
#     (stack(axis=0)); B's COLUMNS are permuted on the host to match,
#     and its ROWS are permuted so the output planes also come out
#     (bit, chunk)-major for the cheap pack
#   * pack="or": unrolled shift-or over contiguous row blocks — no
#     reshape, no transpose, no weighted sum
#   * pack="vpu": reshape+scale+sum on the vector unit
#   * pack="mxu": packed = P @ planes as a second tiny matmul (P holds
#     the 2^b weights), riding the otherwise idle MXU

_EC_TILE = 32768          # default lanes per grid step (mult. of 128)
_EC_LAYOUT = "bc"
_EC_PACK = "or"

#: per-bitmatrix-shape overrides, keyed by the [8r, 8k] bitmat shape:
#: encode (parity rows of the generator) and decode (square-ish
#: rebuild matrices) present DIFFERENT matmul aspect ratios, and the
#: winning (tile, layout, pack) differs between them — a decode
#: autotune pass installs here without clobbering the encode winner
_EC_SHAPE_CFG: dict = {}


def set_fused_config(tile: int = None, layout: str = None,
                     pack: str = None, shape: tuple = None) -> dict:
    """Set the fused-kernel variant (bench autotune).  With ``shape``
    (a bitmat [8r, 8k] shape tuple) the config binds to that matrix
    shape only; without it the process-wide defaults change."""
    global _EC_TILE, _EC_LAYOUT, _EC_PACK
    if shape is not None:
        base = _EC_SHAPE_CFG.get(tuple(shape),
                                 (_EC_TILE, _EC_LAYOUT, _EC_PACK))
        cfg = (int(tile) if tile else base[0],
               layout or base[1], pack or base[2])
        _EC_SHAPE_CFG[tuple(shape)] = cfg
        return {"tile": cfg[0], "layout": cfg[1], "pack": cfg[2],
                "shape": tuple(shape)}
    if tile:
        _EC_TILE = int(tile)
    if layout:
        _EC_LAYOUT = layout
    if pack:
        _EC_PACK = pack
    return {"tile": _EC_TILE, "layout": _EC_LAYOUT, "pack": _EC_PACK}


def _resolve_fused_config(bitmat_shape: tuple) -> tuple:
    """(tile, layout, pack) for one launch: shape-bound winner first,
    process-wide defaults otherwise."""
    return _EC_SHAPE_CFG.get(tuple(bitmat_shape),
                             (_EC_TILE, _EC_LAYOUT, _EC_PACK))


def _perm_cb_to_bc(n_bytes: int) -> np.ndarray:
    """Index map taking (chunk,bit)-ordered planes to (bit,chunk)."""
    idx = np.arange(8 * n_bytes).reshape(n_bytes, 8).T.reshape(-1)
    return idx


def _ec_fused_kernel(bm_ref, data_ref, out_ref, *, layout: str,
                     pack: str):
    """One L-tile: data [k, T] uint8 -> out [r, T] uint8 in VMEM."""
    data = data_ref[...].astype(jnp.int32)              # [k, T]
    k, T = data.shape
    r8 = bm_ref.shape[0]
    r = r8 // 8
    if layout == "cb":
        # (chunk, bit) interleaved planes
        bits = jnp.stack([(data >> b) & 1 for b in range(8)],
                         axis=1).reshape(k * 8, T).astype(jnp.int8)
    else:
        # (bit, chunk): plain concatenation along a new leading axis —
        # no interleave; bm columns/rows were pre-permuted to match
        bits = jnp.stack([(data >> b) & 1 for b in range(8)],
                         axis=0).reshape(8 * k, T).astype(jnp.int8)
    acc = jax.lax.dot_general(
        bm_ref[...], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)               # [8r, T]
    planes = acc & 1
    if pack == "or":
        # contiguous row-block slices, unrolled shift-or: zero
        # relayout on either side of the matmul
        if layout == "bc":          # rows (bit, chunk): b-major blocks
            packed = planes[0:r]
            for b in range(1, 8):
                packed = packed | (planes[b * r:(b + 1) * r] << b)
        else:                       # rows (chunk, bit): via reshape
            g = planes.reshape(r, 8, T)
            packed = g[:, 0]
            for b in range(1, 8):
                packed = packed | (g[:, b] << b)
        out_ref[...] = packed.astype(jnp.uint8)
        return
    if layout == "cb":
        grouped = planes.reshape(r, 8, T)               # rows (chunk,bit)
    else:
        grouped = planes.reshape(8, r, T).transpose(1, 0, 2)
    if pack == "vpu":
        w = (jnp.int32(1)
             << jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0))
        packed = jnp.sum(grouped * w[None, :, :], axis=1)
    else:
        # MXU pack: [r*T rows? no — fold bit axis via dot] P [1,8]
        w = (jnp.int32(1)
             << jax.lax.broadcasted_iota(jnp.int32, (1, 8), 1)
             ).astype(jnp.float32)
        packed = jax.lax.dot_general(
            w, grouped.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)[0].astype(jnp.int32)
    out_ref[...] = packed.astype(jnp.uint8)


def _apply_bitmatrix_pallas(bitmat: jnp.ndarray, data: jnp.ndarray,
                            interpret: bool = False,
                            tile: Optional[int] = None,
                            layout: Optional[str] = None,
                            pack: Optional[str] = None) -> jnp.ndarray:
    """Thin unjitted wrapper: the config (shape-bound winner, else the
    process-wide globals) is resolved HERE, outside jit, so
    set_fused_config/autotune changes reach every later call —
    resolving it inside the traced function would bake the values
    active at first trace into the cached executable forever."""
    ctile, clay, cpack = _resolve_fused_config(bitmat.shape)
    return _apply_bitmatrix_pallas_jit(
        bitmat, data, interpret, tile or ctile,
        layout or clay, pack or cpack)


#: The fused kernel's name in the profiler's device trace.  XLA names
#: the kernel's custom call after the jitted entry that holds it
#: (``%_apply_bitmatrix_pallas_jit.1 = u8[r,L] custom-call(...)`` on
#: the v5e), and the benchmark finds the kernel's device events by a
#: part of that name (benchmark/readers.py EC_APPLY_MATCH).  The entry
#: below takes its name from HERE, not from whatever the Python
#: function happens to be called, so a refactor cannot silently null
#: the kernel's busy and roofline metrics (tests/test_ec.py).
EC_APPLY_TRACE_NAME = "_apply_bitmatrix_pallas_jit"


def _fused_apply(bitmat: jnp.ndarray, data: jnp.ndarray,
                 interpret: bool, tile: int,
                 layout: str, pack: str) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    r8, k8 = bitmat.shape
    k, L = data.shape
    r = r8 // 8
    if layout == "bc":
        # permute B's columns to consume (bit, chunk) planes and its
        # rows to produce them
        bitmat = bitmat[:, _perm_cb_to_bc(k)][_perm_cb_to_bc(r)]
    pad = (-L) % tile
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    Lp = L + pad
    out = pl.pallas_call(
        partial(_ec_fused_kernel, layout=layout, pack=pack),
        grid=(Lp // tile,),
        in_specs=[
            pl.BlockSpec((r8, k8), lambda i: (0, 0)),
            pl.BlockSpec((k, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((r, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((r, Lp), jnp.uint8),
        interpret=interpret,
    )(bitmat, data)
    return out[:, :L] if pad else out


_fused_apply.__name__ = _fused_apply.__qualname__ = EC_APPLY_TRACE_NAME
_apply_bitmatrix_pallas_jit = jax.jit(
    _fused_apply,
    static_argnames=("interpret", "tile", "layout", "pack"))


@partial(jax.jit, static_argnames=("tile", "layout", "pack"))
def _pallas_probe_sum(bitmat: jnp.ndarray, data: jnp.ndarray,
                      tile: int, layout: str, pack: str) -> jnp.ndarray:
    """Autotuner probe: fused apply + on-device checksum reduce, so
    the timing fetch ships ONE scalar instead of the [r, L] result.
    A module-level jit entry (JIT16): the compile cache keys on
    (operand shapes, variant statics) and survives across autotune
    calls — the old per-variant ``jax.jit(lambda ...)`` built a fresh
    jit object (and a fresh, instantly-dead compile cache) every
    sweep."""
    out = _apply_bitmatrix_pallas_jit(bitmat, data, False, tile,
                                      layout, pack)
    return out.astype(jnp.int32).sum()


#: autotune search space: (tile, layout, pack)
TUNE_SPACE = [
    (32768, "bc", "or"),
    (65536, "bc", "or"),
    (32768, "cb", "or"),
    (32768, "cb", "vpu"),
]


def autotune(mat: np.ndarray, length: int = 1 << 25,
             trials: int = 3, install: str = "global") -> dict:
    """Time every fused variant on the live device and install the
    winner (bench.py tpu_ec runs this before measuring).  Returns the
    winner's {tile, layout, pack, rate_mb_s} plus the variants Mosaic
    refused, by name and error; no variant compiling is an error.

    ``install="global"`` sets the process-wide default (the encode
    pass); ``install="shape"`` binds the winner to THIS matrix's
    bitmat shape only (the decode pass — decode matrices have a
    different aspect ratio and must not clobber the encode winner).

    Each timing is the best of ``trials`` calls on a ``length``-byte
    operand, each run to ``block_until_ready``."""
    import time
    from ceph_tpu.ec.gf256 import expand_to_bitmatrix
    bm = jnp.asarray(expand_to_bitmatrix(np.asarray(mat, np.uint8)),
                     jnp.int8)
    k = mat.shape[1]
    data = jax.device_put(jnp.asarray(
        np.random.default_rng(3).integers(
            0, 256, (k, length // k), dtype=np.uint8)))
    best = None
    refused = []
    for tile, lay, pk in TUNE_SPACE:
        try:
            # device-sync:begin autotuner timing: bench-only code off
            # every event loop; the wait IS the measurement
            _pallas_probe_sum(bm, data, tile, lay,
                              pk).block_until_ready()    # compile + warm
            t_best = float("inf")
            for _ in range(trials):
                t0 = time.perf_counter()
                _pallas_probe_sum(bm, data, tile, lay,
                                  pk).block_until_ready()
                t_best = min(t_best, time.perf_counter() - t0)
            # device-sync:end
        except Exception as e:            # Mosaic refused the variant
            refused.append({"tile": tile, "layout": lay, "pack": pk,
                            "error": f"{type(e).__name__}: {e}"[:300]})
            continue
        rate = length / t_best / 1e6
        if best is None or rate > best["rate_mb_s"]:
            best = {"tile": tile, "layout": lay, "pack": pk,
                    "rate_mb_s": round(rate, 1)}
    if best is None:
        raise RuntimeError(f"no fused variant compiled: {refused}")
    shape = tuple(bm.shape) if install == "shape" else None
    set_fused_config(best["tile"], best["layout"], best["pack"],
                     shape=shape)
    if shape is not None:
        best["shape"] = shape
    best["refused"] = refused
    return best


def _pallas_supported() -> bool:
    """The fused kernel is Mosaic code: it runs on a TPU backend, and
    there a shape Mosaic refuses is an error at the call, never a
    quiet switch to the plain-XLA lowering."""
    return jax.default_backend() == "tpu"


class MatrixApply:
    """A compiled GF(2^8) matrix-apply: out = mat @ chunks over the field.

    One instance per (code matrix); jit caches per chunk length.  Used for
    both encode (parity rows of the generator) and decode (rows from
    gf256.decode_matrix).
    """

    def __init__(self, mat: np.ndarray, fused: Optional[bool] = None):
        self.mat = np.asarray(mat, np.uint8)
        from ceph_tpu.ec.gf256 import expand_to_bitmatrix
        self._bitmat = jnp.asarray(expand_to_bitmatrix(self.mat), jnp.int8)
        self.fused = _pallas_supported() if fused is None else fused
        # retrace-counter identity (common/devstats): one per code
        # matrix — everything else the jit cache keys on rides the
        # per-launch signature
        self._sig = (self.mat.shape, hash(self.mat.tobytes()))

    def _fn(self):
        return _apply_bitmatrix_pallas if self.fused else _apply_bitmatrix

    def __call__(self, chunks) -> np.ndarray:
        out = self.device_call(jnp.asarray(chunks, jnp.uint8))
        # device-sync:begin host-facing entry fetch: op-path callers
        # reach this only through the ec_queue executor (_run_group
        # stays on-device and fetches once per group); bench/codec
        # callers fetch inline by contract
        return np.asarray(out)
        # device-sync:end

    def device_call(self, chunks: jnp.ndarray) -> jnp.ndarray:
        """On-device variant for fused pipelines (no host round-trip)."""
        cfg = (_resolve_fused_config(self._bitmat.shape)
               if self.fused else ())
        devstats.note_launch(
            "ec_apply", (self._sig, tuple(chunks.shape), self.fused,
                         cfg))
        return self._fn()(self._bitmat, chunks)


@lru_cache(maxsize=256)
def _cached_apply(mat_bytes: bytes, r: int, k: int) -> MatrixApply:
    return MatrixApply(np.frombuffer(mat_bytes, np.uint8).reshape(r, k))


def matrix_apply(mat: np.ndarray) -> MatrixApply:
    mat = np.ascontiguousarray(mat, np.uint8)
    return _cached_apply(mat.tobytes(), mat.shape[0], mat.shape[1])
