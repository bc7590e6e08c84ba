"""Cross-PG device dispatch queue: coalesced EC encodes on the TPU.

This is SURVEY §7's hard part — "a 4KiB-chunk op can't pay a dispatch
each; requires batching queues (the reference's ShardedOpWQ becomes a
batch-collector feeding the TPU)" — and the north-star integration the
reference runs per-op on CPU SIMD (osd/ECBackend.cc:1344 →
ECUtil::encode → erasure-code/isa/ErasureCodeIsa.cc:153 per stripe).

Design:
  * PG workers await `apply(mat, chunks)`; requests park in a pending
    list while a collector task lets the batch fill for a short window
    (osd_ec_batch_window_ms — bounded latency cost).
  * GF(2^8) matrix applies are lane-independent, so requests sharing a
    generator matrix CONCATENATE along the lane axis regardless of their
    individual lengths: one [k, ΣL] device launch encodes stripes from
    many PGs (and many objects) at once.
  * The folded batch is cut on the HOST into launch windows, each a
    [k, b] buffer with b in LANE_BUCKETS (the tail padded there),
    and each window is staged, launched and fetched whole: no device
    op ever takes the group's own width, so the compiled programs are
    one per (matrix shape, lane bucket) whatever mix of sizes arrives.
    The device call (fused pallas kernel on TPU, XLA elsewhere —
    ec/kernel.py) runs in a single-thread executor so the event loop
    never blocks on the device.
  * Small lone requests take the native host kernel (GFNI/AVX-512)
    instead: a window plus a device dispatch costs more latency than
    encoding 64 KiB on the CPU.  Everything is counted in perf
    counters so `perf dump` proves where bytes went.  What that
    threshold (osd_ec_batch_min_bytes) SHOULD be is measured in the
    benchmark's cell `ycsb_a_1k_zipf` (1,000-byte records, the
    threshold stated as 0 so every encode takes the device):
    `device_lanes_launched` beside `device_bytes` says how much of
    each launch was padding up to its lane bucket (pad share =
    1 - (device_bytes / k) / device_lanes_launched; PERF.md
    sections 5 and 7).
  * A request may carry a continuation (`apply_then`): what the
    caller would do first with the rows, `finish(chunks, rows)`, runs
    where the rows are made — on the ec-device thread for a device
    group, so that work is off the event loop too.  The rows still
    come back through `apply()`, carrying what the continuation made
    of them.
  * A request's input is a [k, L] array (an encode's split data) or
    k equal-length 1-D rows (a decode's survivors, views of the shard
    replies): rows are copied once, into the folded batch on the
    ec-device thread; only the host kernel stacks them.
  * Whether the queue launches on the device at all is decided ONCE,
    by `resolve_backend()`, before the OSD takes ops: "on" without an
    accelerator fails the start, and every later device->host reroute
    is an error counted in `device_fallbacks`.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import contextvars
from typing import Callable, Dict, List, Optional, Sequence, Union

import numpy as np

#: launch-window widths: at most this many compiled shapes per
#: generator matrix shape (the largest repeats for oversize groups)
LANE_BUCKETS = (1 << 14, 1 << 16, 1 << 18, 1 << 20, 1 << 22)

#: host bytes of launch windows one queue keeps from group to group
#: (`ECBatchQueue._window_buffers`): the eight [2, 2^22] windows of a
#: 64 MiB k=2 write, or the four [4, 2^22] of sixteen 4 MiB k=4 ones,
#: beside one window of each smaller bucket; a wider group's other
#: windows are made for it alone
WINDOW_POOL_BYTES = 72 << 20


def _bucket(n: int) -> int:
    for b in LANE_BUCKETS:
        if n <= b:
            return b
    return LANE_BUCKETS[-1]


def _windows(total: int) -> List[int]:
    """The widths, in lanes, of the launch windows of a group of
    `total` lanes, in fold order: the largest bucket while the rest
    overfills it, then the bucket that holds the rest.  Every window
    costs the ec-device thread a transfer each way and a launch, each a
    hand-over of the GIL beside the busy loop, so a tail is padded up
    to its bucket rather than split into more windows."""
    if total <= 0:
        return []
    top = LANE_BUCKETS[-1]
    full, rest = divmod(total - 1, top)
    return [top] * full + [_bucket(rest + 1)]


class _Req:
    __slots__ = ("key", "mat", "chunks", "lanes", "finish", "fut",
                 "t_apply", "t_done")

    def __init__(self, key, mat, chunks, lanes, finish, fut, t_apply):
        self.key = key
        self.mat = mat
        self.chunks = chunks        # [k, L] uint8, or k rows of L
        self.lanes = lanes          # L
        self.finish = finish        # continuation or None
        self.fut = fut
        # op tracing (tracer stamps; 0.0 while tracing is off):
        # apply() entry, and the executor's last instant on the group
        self.t_apply = t_apply
        self.t_done = 0.0


#: the continuation of the apply() this task is making: apply_then()
#: sets it around its call, apply() hands it to the request
_FINISH: contextvars.ContextVar = contextvars.ContextVar(
    "ec_apply_finish", default=None)

_NOT_MADE = object()


class _Rows(np.ndarray):
    """A request's result rows that carry what its continuation made of
    them.  Only the very object the seam returned does: a copy or a
    view of it is an array without one again."""
    finished = _NOT_MADE


class ECBatchQueue:
    """OSD-wide EC encode/decode coalescer (one per daemon)."""

    def __init__(self, ctx, mode: str = "auto", window_ms: float = 2.0,
                 min_device_bytes: int = 64 * 1024,
                 max_pending_bytes: int = 256 << 20,
                 flush_bytes: int = 4 << 20):
        self.ctx = ctx
        self.logger = ctx.logger("ec")
        self.window = window_ms / 1000.0
        self.min_device_bytes = min_device_bytes
        self.flush_bytes = flush_bytes
        self.mode = mode
        self._pending: List[_Req] = []
        self._pending_bytes = 0
        # bound the park lot: more encode bytes than this in flight and
        # new apply() callers BLOCK (FIFO) until a batch drains — an
        # unbounded pending list let a fast client balloon OSD memory
        from ceph_tpu.common.throttle import AsyncThrottle
        self._pending_throttle = AsyncThrottle("ec_pending_bytes",
                                               max_pending_bytes)
        self._wake: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ec-device")
        self.perf = ctx.perf.create("ec_batch_queue")
        for key in ("device_launches", "device_requests", "device_bytes",
                    # the bucketed lanes of every device_call: what
                    # was launched, against device_bytes / k asked for,
                    # and the difference, the lanes padded on the host
                    "device_lanes_launched", "device_pad_lanes",
                    "host_requests", "host_bytes", "device_fallbacks",
                    # continuations run on the ec-device thread / on
                    # the caller's thread (host kernel, fallback)
                    "finish_thread", "finish_inline",
                    # requests handed in as rows, not as one array
                    "row_requests"):
            self.perf.add_u64(key)
        self.perf.add_avg("batch_fill")    # requests per device launch
        # concurrent encodes parked in the collector at each arrival:
        # with the per-PG op window (osd_pg_max_inflight_ops) every PG
        # contributes several stripes, so mean pending_depth > 1 is
        # the batch collector actually filling (it never could when
        # each PG held one op in flight)
        self.perf.add_avg("pending_depth")
        self._device_ok: Optional[bool] = None
        # the launch windows' host buffers, per (rows, lanes), kept from
        # group to group (`_window_buffers`), and their bytes
        self._window_bufs: Dict[tuple, List[np.ndarray]] = {}
        self._window_bytes = 0

    # ------------------------------------------------------------- policy
    def resolve_backend(self) -> bool:
        """Decide, once, whether this queue launches on the device.

        Modes: "off" = host always; "force" = any jax backend, even the
        CPU one (tests exercise the device code path without a TPU);
        "on" = a real accelerator is REQUIRED — without one this
        raises, which fails the OSD start; "auto" = the device when the
        process's jax backend is an accelerator, the host otherwise (on
        a CPU jax backend the device path pays dispatch + fill-window
        latency to run the same bytes slower than the native
        GFNI/AVX-512 kernel).

        Blocking (the first call may import jax and initialise the
        runtime): start() runs it off the loop before the OSD takes
        ops, so apply() only ever reads the cached answer."""
        if self._device_ok is None:
            from ceph_tpu.common import envutil
            accel = self.mode in ("on", "auto") \
                and envutil.accelerator_present()
            if self.mode == "on" and not accel:
                raise RuntimeError(
                    "osd_ec_batch_device=on requires an accelerator, "
                    "and this process's jax backend is the CPU")
            if accel:
                cache = envutil.enable_compile_cache()
                self.logger.info(
                    f"EC batch device on; compile cache at {cache}")
            self._device_ok = accel or self.mode == "force"
        return self._device_ok

    async def start(self) -> None:
        """Resolve the backend before the first request.  Asking jax
        for its backend can take seconds (import, device runtime
        init), so that happens on this queue's device thread, off the
        loop.  A process pinned to the CPU — every test, vstart daemon
        and lane worker — answers without jax and without a thread
        hop (the sim loop must not wait on one)."""
        from ceph_tpu.common import envutil
        if self.mode in ("on", "auto") and not envutil.pinned_to_cpu():
            await asyncio.get_running_loop().run_in_executor(
                self._pool, self.resolve_backend)
        else:
            self.resolve_backend()

    def note_fallback(self, what: str, err: BaseException) -> None:
        """A device launch failed and its bytes are being re-run on a
        slower path.  The reroute keeps the op alive; the counter and
        the error line keep it from passing for device work."""
        self.perf.inc("device_fallbacks")
        self.logger.error(f"{what} failed ({err!r}); rerouting off "
                          f"the device")

    # ---------------------------------------------------------------- api
    async def apply(self, mat: np.ndarray,
                    chunks: Union[np.ndarray, Sequence[np.ndarray]]
                    ) -> np.ndarray:
        """out[r, L] = mat @ chunks over GF(2^8), batched across callers.

        `chunks` is a [k, L] array, or a sequence of k equal-length 1-D
        uint8 rows, which are read where they lie: the device path
        copies each into its slice of the folded batch, and only the
        host kernel stacks them.

        Single awaitable entry for PG backends; takes the native host
        kernel when the device isn't worth it (small lone request) or
        isn't this queue's backend (mode=off, auto without an
        accelerator)."""
        tr = self.ctx.tracer
        t_apply = tr.stamp()
        if isinstance(chunks, np.ndarray):
            chunks = np.ascontiguousarray(chunks, np.uint8)
            lanes = chunks.shape[1]
        else:
            self.perf.inc("row_requests")
            lanes = len(chunks[0])
        nbytes = len(chunks) * lanes
        if (not self.resolve_backend()
                or (nbytes < self.min_device_bytes
                    and not self._pending)):
            out = self._host_apply(mat, chunks, nbytes)
            tr.interval("seam_apply", t_apply)
            return out
        loop = asyncio.get_running_loop()
        if self._wake is None:
            self._wake = asyncio.Event()
        await self._pending_throttle.get(nbytes)
        fut = loop.create_future()
        req = _Req((mat.shape, mat.tobytes()),
                   np.ascontiguousarray(mat, np.uint8), chunks, lanes,
                   _FINISH.get(), fut, t_apply)
        self._pending.append(req)
        self._pending_bytes += nbytes
        self.perf.tinc("pending_depth", len(self._pending))
        self._wake.set()
        if self._task is None or self._task.done():
            self._task = loop.create_task(self._collector())
        try:
            out = await fut
            # how long the finished result waited for the loop, and
            # the whole await the seam's other stages tile
            tr.interval("seam_resume", req.t_done)
            tr.interval("seam_apply", t_apply)
            return out
        finally:
            self._pending_throttle.put(nbytes)

    def _host_apply(self, mat, chunks, nbytes) -> np.ndarray:
        self.perf.inc("host_requests")
        self.perf.inc("host_bytes", nbytes)
        from ceph_tpu.common import devstats
        devstats.note_bytes("ec_apply", nbytes, device=False)
        if not isinstance(chunks, np.ndarray):
            chunks = np.stack(chunks)
        from ceph_tpu import native
        if native.available():
            return native.gf_matrix_apply(mat, chunks)
        from ceph_tpu.ec import gf256
        return gf256.host_apply(mat, chunks)

    async def apply_then(self, mat: np.ndarray, chunks: np.ndarray,
                         finish: Callable):
        """`finish(chunks, rows)` of one apply(): what the caller would
        do first with the rows, made where the rows are made.  For a
        device group that is the ec-device thread (section
        `seam_finish`): `rows` is then a view of a fetched window's
        result (its pieces joined, where the request ran over windows),
        so `finish` copies what it keeps, writes to neither argument,
        and what it raises fails this request alone.

        The rows come back through apply() all the same, so whatever
        stands in front of it sees them, and only the very rows the
        seam returned vouch for what was made of them: rows made on
        this thread (the host kernel; the reroute after a device
        failure) or replaced on their way here get `finish` inline,
        which on a loop is EC host work and is named so."""
        token = _FINISH.set(finish)
        try:
            rows = await self.apply(mat, chunks)
        finally:
            _FINISH.reset(token)
        done = getattr(rows, "finished", _NOT_MADE)
        if done is _NOT_MADE:
            self.perf.inc("finish_inline")
            with self.ctx.tracer.section("loop_ec_host"):
                done = finish(chunks, rows)
        return done

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass
            self._task = None
        self._pool.shutdown(wait=False)

    # ---------------------------------------------------------- collector
    async def _collector(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            if not self._pending:
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), 30.0)
                except asyncio.TimeoutError:
                    # a request can slip in while the timer fires and
                    # apply() won't respawn (task not done yet): only
                    # die when the pending list is truly empty
                    if self._pending:
                        continue
                    return   # idle: task dies, re-spawned on demand
            # adaptive fill: wait at most `window`, but flush the moment
            # the bytes-quorum lands — the latency cost is only paid
            # while it is actually buying batching (VERDICT r4 #2)
            deadline = loop.time() + self.window
            while self._pending_bytes < self.flush_bytes:
                rem = deadline - loop.time()
                if rem <= 0:
                    break
                self._wake.clear()
                try:
                    await asyncio.wait_for(self._wake.wait(), rem)
                except asyncio.TimeoutError:
                    break
            batch, self._pending = self._pending, []
            self._pending_bytes = 0
            groups: Dict[bytes, List[_Req]] = {}
            for r in batch:
                groups.setdefault(r.key, []).append(r)
            for reqs in groups.values():
                try:
                    outs = await loop.run_in_executor(
                        self._pool, self._run_group, reqs)
                    for r, out in zip(reqs, outs):
                        if r.fut.done():
                            continue
                        if isinstance(out, Exception):
                            # what its continuation raised: its alone
                            r.fut.set_exception(out)
                        else:
                            r.fut.set_result(out)
                except Exception as e:     # device failure: host fallback
                    self.note_fallback("device batch", e)
                    for r in reqs:
                        if not r.fut.done():
                            try:
                                nb = len(r.chunks) * r.lanes
                                r.fut.set_result(
                                    self._host_apply(r.mat, r.chunks, nb))
                            except Exception as e2:
                                r.fut.set_exception(e2)

    def _window_buffers(self, k: int, widths: List[int]) -> list:
        """Host buffers for a group's launch windows, [k, b] each, kept
        and reused by later groups: a fresh buffer of a large bucket
        costs its page faults on every group, beside a busy loop and
        threads that write to disk.  Reuse is safe because only this
        executor thread fills them and a group's fetch waits for its
        kernels, so for their input transfers, before the next group is
        folded.  The queue keeps at most `WINDOW_POOL_BYTES` of them,
        the first made; a window past that is made for its group alone
        and dropped with it.  `_fold` zeroes the lanes a reused buffer
        holds past the group's last."""
        out, taken = [], {}
        for b in widths:
            pool = self._window_bufs.setdefault((k, b), [])
            i = taken[k, b] = taken.get((k, b), -1) + 1
            if i < len(pool):
                out.append(pool[i])
                continue
            buf = np.zeros((k, b), np.uint8)
            if self._window_bytes + buf.nbytes <= WINDOW_POOL_BYTES:
                pool.append(buf)
                self._window_bytes += buf.nbytes
            out.append(buf)
        return out

    def _run_group(self, reqs: List[_Req]) -> list:
        """Executor thread: device launches for all requests sharing a
        generator matrix, folded along the lane axis.

        The fold goes straight into the launch windows (`_windows`):
        host buffers of [k, b] lanes, b a lane bucket, kept from group
        to group (`_window_buffers`).  Each window is staged (declared
        ``device_put``) and launched as it stands, and the results come
        home in one declared fetch region, a window at a time, and are
        split into the requests' rows on the host.  No device op here takes the
        group's own width or an offset into it, so every program that
        runs is one of at most ``len(LANE_BUCKETS)`` per matrix shape,
        compiled once, whatever widths the groups have."""
        import jax
        from ceph_tpu.ec.kernel import matrix_apply
        tr = self.ctx.tracer
        if tr.enabled:
            # the executor takes the group: the collector's window and
            # the wait behind the groups launched before it end here
            for r in reqs:
                tr.interval("seam_pending", r.t_apply)
        mat = reqs[0].mat
        total = sum(r.lanes for r in reqs)
        k = len(reqs[0].chunks)
        widths = _windows(total)
        with tr.section("seam_fold"):
            bufs = self._window_buffers(k, widths)
            spans = _fold(reqs, bufs)
        ap = matrix_apply(mat)
        outs = []
        # device-candidate:ec-dispatch@landed the live executor-side launch:
        # the group folded on the host into LANE_BUCKETS-wide windows,
        # each staged and launched whole and fetched whole (the shape
        # every candidate above adopts)
        for buf in bufs:
            t0 = tr.stamp()
            # XFER17 staging transfer: one h2d per window
            with tr.section("seam_h2d"):
                dev = jax.device_put(buf)
            with tr.section("seam_launch"):
                outs.append(ap.device_call(dev))
            tr.interval("seam_window", t0)
        # device-sync:begin group result fetch: one d2h per window, on
        # the ec-device executor thread — the event loop only awaits
        # run_in_executor
        with tr.section("seam_d2h"):
            fetched = [np.asarray(o) for o in outs]
        # device-sync:end
        launched = sum(widths)
        self.perf.inc("device_launches", len(widths))
        self.perf.inc("device_lanes_launched", launched)
        self.perf.inc("device_pad_lanes", launched - total)
        self.perf.inc("device_requests", len(reqs))
        self.perf.inc("device_bytes", k * total)
        # LIVE device_byte_fraction substrate (metrics plane): booked
        # only AFTER the fetch proved every launch succeeded — a
        # device failure falls back to _host_apply, which must not
        # find these bytes already counted as device work
        from ceph_tpu.common import devstats
        devstats.note_bytes("ec_apply", k * total, device=True)
        self.perf.tinc("batch_fill", len(reqs))
        # a request's rows: a view of its window's result, or, where it
        # ran over windows, its pieces joined
        rows = []
        for parts in spans:
            pieces = [fetched[w][:, a:b] for w, a, b in parts]
            rows.append(pieces[0] if len(pieces) == 1 else np.concatenate(
                pieces or [np.zeros((mat.shape[0], 0), np.uint8)], axis=1))
        res: list = [None] * len(reqs)
        todo = [i for i, r in enumerate(reqs) if r.finish is not None]
        if len(todo) < len(reqs):
            with tr.section("seam_split"):
                for i, r in enumerate(reqs):
                    if r.finish is None:
                        res[i] = np.ascontiguousarray(rows[i])
        if todo:
            # a continuation takes its rows as a view of the fetched
            # batch (what it keeps it copies itself: the only copy),
            # and so they go back, with what it made of them; what it
            # raises is its own request's error, never a device failure
            with tr.section("seam_finish"):
                for i in todo:
                    done = rows[i].view(_Rows)
                    try:
                        done.finished = reqs[i].finish(reqs[i].chunks,
                                                       rows[i])
                    except Exception as e:
                        done = e
                    res[i] = done
            self.perf.inc("finish_thread", len(todo))
        # the results are finished; from here they wait for the loop
        # (the collector's resume, then each awaiter's)
        t_done = tr.stamp()
        for r in reqs:
            r.t_done = t_done
        return res


def _fold(reqs: List[_Req], bufs: List[np.ndarray]) -> list:
    """Copy the requests' lanes, in order, into the host windows `bufs`,
    and zero the padded tail of the last.  Returns, per request, the
    pieces it landed in: [(window, first lane, end lane)]."""
    widths = [buf.shape[1] for buf in bufs]
    flats = [memoryview(buf.reshape(-1)) for buf in bufs]
    spans = []
    w = at = 0
    for r in reqs:
        parts = []
        done = 0
        while done < r.lanes:
            n = min(r.lanes - done, widths[w] - at)
            if isinstance(r.chunks, np.ndarray):
                bufs[w][:, at:at + n] = r.chunks[:, done:done + n]
            else:
                # a memoryview copy keeps the GIL: a numpy copy per
                # row would give it up, and wait to win it back from
                # the busy loop, once per row
                b, flat = widths[w], flats[w]
                for i, row in enumerate(r.chunks):
                    flat[i * b + at:i * b + at + n] = row[done:done + n]
            parts.append((w, at, at + n))
            done += n
            at += n
            if at == widths[w]:
                w, at = w + 1, 0
        spans.append(parts)
    if at:
        bufs[w][:, at:] = 0
    return spans
