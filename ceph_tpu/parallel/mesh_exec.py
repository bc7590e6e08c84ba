"""OSD device-mesh execution mode: co-located shard OSDs share a jax
device mesh, and the EC write path runs as ONE sharded device program
instead of host encode + per-shard messenger sends.

Reference mapping (SURVEY §2.4 TPU-native design): the bulk-data hop of
ECBackend::submit_transaction — encode then MOSDECSubOpWrite to every
shard OSD (/root/reference/src/osd/ECBackend.cc:1344,1773) — becomes

  * a shard_map'd GF(2^8) encode where device i COMPUTES shard i's
    bytes in place: data chunks all_gather along the mesh's "shard"
    axis (the ICI hop that replaces the NCCL-less messenger fan-out),
    each device applies its own generator row block, so when the
    program ends every device holds exactly its shard;
  * in-process delivery of the per-shard sub-op (log append + store
    txn) to the co-located OSD — the chunk bytes never touch TCP.

Control traffic (acks, maps, peering) stays on the messenger — the
data/control split the survey prescribes.  OSDs not registered on the
executor (remote hosts) still get messenger sends, so a partially
co-located cluster degrades to the normal path per target.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Dict, Optional

import numpy as np

_EXECUTOR: Optional["MeshExecutor"] = None


def enable() -> "MeshExecutor":
    """Install the process-wide executor (vstart/in-process clusters)."""
    global _EXECUTOR
    if _EXECUTOR is None:
        _EXECUTOR = MeshExecutor()
    return _EXECUTOR


def disable() -> None:
    global _EXECUTOR
    _EXECUTOR = None


def current() -> Optional["MeshExecutor"]:
    return _EXECUTOR


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@lru_cache(maxsize=32)
def _mesh_recover_fn(n_surv: int, n_want: int, mat_bytes: bytes):
    """Jitted pjit decode-rebuild (layout.ec_recover_step) for a decode
    matrix reconstructing n_want chunks from n_surv survivors.  The
    host x shard mesh is sized so 'shard' divides the survivor count
    (single-device runs collapse to 1x1).  Returns (fn, host_dim) —
    callers pad the stripe batch axis to a host_dim multiple."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ceph_tpu.ec.gf256 import expand_to_bitmatrix
    from ceph_tpu.parallel.layout import ec_recover_step

    mat = np.frombuffer(mat_bytes, np.uint8).reshape(n_want, n_surv)
    bitmat = jnp.asarray(expand_to_bitmatrix(mat), jnp.int8)
    devs = jax.devices()
    shard = 1
    for cand in (8, 4, 2, 1):
        if n_surv % cand == 0 and len(devs) % cand == 0:
            shard = cand
            break
    host = len(devs) // shard
    grid = np.empty(host * shard, dtype=object)
    grid[:] = devs[:host * shard]
    mesh = Mesh(grid.reshape(host, shard), ("host", "shard"))
    return ec_recover_step(mesh, bitmat, n_surv), host


@lru_cache(maxsize=32)
def _mesh_encode_fn(n: int, k: int, mat_bytes: bytes):
    """Jitted sharded encode for an n-device 1-D mesh: in [n, Lc] chunk
    rows (parity rows zero), out [n, Lc] with device i holding shard i.
    Cached per (geometry, generator)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from ceph_tpu.ec.gf256 import expand_to_bitmatrix

    gen = np.frombuffer(mat_bytes, np.uint8).reshape(n, k)
    # per-shard 8-row bit-matrix blocks: blocks[i] computes shard i
    # from the k data chunks (identity passthrough for data shards)
    bitmat = expand_to_bitmatrix(gen)              # [8n, 8k]
    blocks = jnp.asarray(bitmat.reshape(n, 8, 8 * k), jnp.int8)

    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"mesh mode needs {n} devices, "
                           f"have {len(devs)}")
    grid = np.empty(n, dtype=object)
    grid[:] = devs[:n]
    mesh = Mesh(grid, ("shard",))

    def step(local):                                # local [1, Lc] uint8
        # the ICI hop: every device receives all k data chunks
        # (replaces the messenger's per-shard chunk send)
        allg = jax.lax.all_gather(local, "shard")   # [n, 1, Lc]
        data = allg[:k, 0]                          # [k, Lc]
        idx = jax.lax.axis_index("shard")
        blk = jnp.take(blocks, idx, axis=0)         # [8, 8k]
        # unpack -> per-device row-block matmul -> mod2 -> pack
        kk, L = data.shape
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = ((data[:, None, :] >> shifts[None, :, None]) & 1) \
            .reshape(kk * 8, L).astype(jnp.int8)
        acc = jax.lax.dot_general(
            blk, bits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)       # [8, L]
        planes = (acc & 1).astype(jnp.uint8)
        out = planes[0]
        for b in range(1, 8):
            out = out | (planes[b] << b)
        return out[None, :]                         # [1, L]

    fn = shard_map(step, mesh=mesh,
                   in_specs=(P("shard", None),),
                   out_specs=P("shard", None),
                   check_vma=False)
    return jax.jit(fn), mesh


class MeshExecutor:
    """Process-wide registry of co-located OSDs + the sharded encode."""

    def __init__(self):
        import concurrent.futures
        self.osds: Dict[int, object] = {}
        self.launches = 0
        self.inproc_subops = 0
        # device dispatch (and the first-call jit compile) must never
        # run on the shared event loop every co-located OSD lives on
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="mesh-exec")
        # decode-rebuild collector state, keyed per event loop (threaded
        # shards each run their own loop; futures must stay loop-local)
        self._rec_pending: Dict[int, list] = {}
        self._rec_tasks: Dict[int, object] = {}

    def register(self, osd) -> None:
        self.osds[osd.whoami] = osd

    def unregister(self, osd_id: int) -> None:
        self.osds.pop(osd_id, None)

    def covers(self, osd_id: int) -> bool:
        return osd_id in self.osds

    # ------------------------------------------------------------- encode
    async def encode_object(self, codec,
                            data: bytes) -> Dict[int, np.ndarray]:
        """Full-object encode as one sharded device program; returns
        shard index -> chunk bytes (same contract as codec.encode).
        The launch runs in the executor thread — the event loop only
        awaits it."""
        import asyncio
        gen = getattr(codec, "generator", None)
        if gen is None:
            raise RuntimeError("codec exposes no generator matrix")
        k = codec.get_data_chunk_count()
        n = codec.get_chunk_count()
        chunks = codec.split_data(data)             # [k, Lc]
        Lc = len(chunks[0])

        def _launch():
            from ceph_tpu.common import devstats
            mat_bytes = np.ascontiguousarray(gen, np.uint8).tobytes()
            fn, _mesh = _mesh_encode_fn(n, k, mat_bytes)
            inp = np.zeros((n, Lc), np.uint8)
            for i in range(k):
                inp[i] = chunks[i]
            devstats.note_launch("mesh_encode",
                                 (n, k, hash(mat_bytes), Lc))
            # device-sync:begin sharded-encode fetch: this closure runs
            # on the mesh executor's own thread (run_in_executor above)
            # — the event loop only awaits the handoff
            return np.asarray(fn(inp))
            # device-sync:end

        out = await asyncio.get_running_loop().run_in_executor(
            self._pool, _launch)
        self.launches += 1
        return {i: out[i] for i in range(n)}

    # ------------------------------------------------------------ recover
    async def recover_chunks(self, codec, want,
                             streams: Dict[int, np.ndarray]) -> Dict[int, np.ndarray]:
        """Decode-rebuild twin of encode_object: reconstruct the `want`
        chunk ids from the survivor `streams` as ONE pjit recovery
        program (layout.ec_recover_step).  Requests parking in the same
        fill window that share a decode matrix stack along the stripe
        batch axis — PG._recover's concurrent backfill window and
        concurrent degraded reads fold into a single device launch."""
        import asyncio
        gen = getattr(codec, "generator", None)
        if gen is None:
            raise RuntimeError("codec exposes no generator matrix")
        k = codec.get_data_chunk_count()
        present = sorted(streams)[:k]
        out = {w: np.asarray(streams[w], np.uint8)
               for w in want if w in streams}
        missing = [w for w in want if w not in streams]
        if not missing:
            return out
        if len(present) < k:
            # same contract as ECBackend._decode_shards: an
            # under-gathered survivor set must fail loudly, not feed an
            # empty submatrix into the decode program
            raise ValueError(
                f"need {k} shards to decode, have {len(present)}")
        mat = codec.decode_matrix_for(present, missing)    # [n_want, k]
        surv = np.stack([np.ascontiguousarray(streams[i], np.uint8)
                         for i in present])                # [n_surv, L]
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        key = (surv.shape[0], len(missing),
               np.ascontiguousarray(mat, np.uint8).tobytes())
        # dict.setdefault is gil-atomic; each loop only touches its own
        # id(loop) slot (same discipline as daemon._recovery_budgets)
        self._rec_pending.setdefault(id(loop), []).append(
            (key, surv, fut))
        task = self._rec_tasks.get(id(loop))
        if task is None or task.done():
            self._rec_tasks[id(loop)] = loop.create_task(
                self._rec_drain(id(loop)))
        lost = await fut                                   # [n_want, L]
        for i, w in enumerate(missing):
            out[w] = lost[i]
        return out

    async def _rec_drain(self, loop_key: int) -> None:
        """Fill window + group dispatch for parked rebuild decodes."""
        import asyncio
        # one tick lets every pull issued by the same recovery window
        # park; the short sleep lets cross-task degraded reads pile on
        await asyncio.sleep(0.002)
        batch = self._rec_pending.pop(loop_key, [])
        if not batch:
            return
        groups: Dict[tuple, list] = {}
        for key, surv, fut in batch:
            groups.setdefault(key, []).append((surv, fut))
        loop = asyncio.get_running_loop()
        for key, reqs in groups.items():
            try:
                outs = await loop.run_in_executor(
                    self._pool, self._rec_launch, key,
                    [s for s, _ in reqs])
                for (_, fut), o in zip(reqs, outs):
                    if not fut.done():
                        fut.set_result(o)
            except Exception as e:
                for _, fut in reqs:
                    if not fut.done():
                        fut.set_exception(e)
                # a multiply-awaited exception must not raise "never
                # retrieved" warnings for callers that already bailed
                for _, fut in reqs:
                    if fut.done():
                        fut.exception()

    def _rec_launch(self, key: tuple, survs: list) -> list:
        """Executor thread: one sharded decode launch for every parked
        request sharing a decode matrix.  Stripes stack along the batch
        ('host'-sharded) axis, padded to a host-multiple power of two;
        lanes pad to a power-of-two bucket — both bound the jit cache."""
        from ceph_tpu.common import devstats
        n_surv, n_want, mat_bytes = key
        fn, host = _mesh_recover_fn(n_surv, n_want, mat_bytes)
        lens = [s.shape[1] for s in survs]
        B = len(survs)
        Bp = host * _pow2_at_least(-(-B // host))
        Lp = max(4096, _pow2_at_least(max(lens)))
        inp = np.zeros((Bp, n_surv, Lp), np.uint8)
        for i, s in enumerate(survs):
            inp[i, :, :s.shape[1]] = s
        devstats.note_launch(
            "decode_rebuild", (n_surv, n_want, hash(mat_bytes), Bp, Lp))
        # device-sync:begin batched decode-rebuild fetch: this runs on
        # the mesh executor's own thread (run_in_executor above) — the
        # event loop only awaits the handoff
        lost, _scrub = fn(inp)
        out = np.asarray(lost)                 # [Bp, n_want, Lp]
        # device-sync:end
        devstats.note_bytes("decode_rebuild", n_surv * sum(lens),
                            device=True)
        self.launches += 1
        return [np.ascontiguousarray(out[i, :, :lens[i]])
                for i in range(B)]

    # ----------------------------------------------------------- delivery
    def deliver(self, target_osd_id: int, msg, from_osd: int) -> bool:
        """Hand a sub-op to a co-located OSD without the messenger (the
        bulk-bytes hop).  Returns False if the target isn't local (the
        caller falls back to a messenger send).  Acks ride the normal
        messenger — only the chunk bytes skip TCP."""
        osd = self.osds.get(target_osd_id)
        if osd is None or not osd.running:
            return False
        # stamp what the transport would have (replies address src_name)
        import time as _time
        from ceph_tpu.msg.types import EntityName
        msg.recv_stamp = _time.monotonic()
        msg.src_name = EntityName("osd", str(from_osd))
        sender = self.osds.get(from_osd)
        if sender is not None:
            msg.src_addr = sender.messenger.addr
        self.inproc_subops += 1
        try:
            return bool(osd.ms_dispatch(msg))
        except Exception:
            return False
