"""Cross-PG device batch collector (osd/ec_queue.py).

Unit: coalescing, correctness vs the host kernel, backend resolution and
host-fallback policy, perf accounting.  E2E: a live in-process cluster
with osd_ec_batch_device=force proves client writes on an EC pool flow
through the device queue (device_bytes > 0 on the primary, results
readable).
The jit path runs on the CPU backend here; the identical code hits the
fused pallas kernel on TPU.
"""

import asyncio
import sys

import numpy as np
import pytest

from ceph_tpu.common.context import Context
from ceph_tpu.ec import gf256
from ceph_tpu.osd.ec_queue import ECBatchQueue


def make_queue(mode="force", window_ms=5.0, min_device_bytes=1 << 16):
    ctx = Context("osd.0")
    return ECBatchQueue(ctx, mode=mode, window_ms=window_ms,
                        min_device_bytes=min_device_bytes)


def gen_mat(k=4, m=2):
    return gf256.rs_vandermonde_matrix(k, m)[k:]


def test_concurrent_requests_coalesce_into_one_launch():
    async def run():
        q = make_queue(min_device_bytes=256)
        mat = gen_mat()
        rng = np.random.default_rng(0)
        ins = [rng.integers(0, 256, (4, 1000 + 128 * i), dtype=np.uint8)
               for i in range(8)]
        outs = await asyncio.gather(*[q.apply(mat, c) for c in ins])
        for c, o in zip(ins, outs):
            assert np.array_equal(o, gf256.host_apply(mat, c))
        d = q.perf.dump()
        assert d["device_requests"] == 8
        assert d["device_launches"] == 1          # ONE folded launch
        assert d["device_bytes"] == sum(4 * c.shape[1] for c in ins)
        await q.stop()
    asyncio.run(run())


def test_mixed_matrices_group_separately():
    async def run():
        q = make_queue(min_device_bytes=256)
        m1, m2 = gen_mat(4, 2), gen_mat(2, 1)
        rng = np.random.default_rng(1)
        c1 = rng.integers(0, 256, (4, 3000), dtype=np.uint8)
        c2 = rng.integers(0, 256, (2, 5000), dtype=np.uint8)
        o1, o2 = await asyncio.gather(q.apply(m1, c1), q.apply(m2, c2))
        assert np.array_equal(o1, gf256.host_apply(m1, c1))
        assert np.array_equal(o2, gf256.host_apply(m2, c2))
        assert q.perf.dump()["device_launches"] == 2
        await q.stop()
    asyncio.run(run())


def test_small_lone_request_takes_host_path():
    async def run():
        q = make_queue(min_device_bytes=1 << 20)
        mat = gen_mat()
        c = np.arange(4 * 512, dtype=np.uint8).reshape(4, 512)
        out = await q.apply(mat, c)
        assert np.array_equal(out, gf256.host_apply(mat, c))
        d = q.perf.dump()
        assert d["host_requests"] == 1 and d["device_requests"] == 0
        await q.stop()
    asyncio.run(run())


def test_oversize_batch_splits_into_bucket_windows():
    # total lanes beyond the largest bucket: must split into multiple
    # launches, not fail over to the host path
    from ceph_tpu.osd import ec_queue as eq

    async def run():
        q = make_queue(min_device_bytes=256)
        mat = gen_mat(2, 1)
        cap = eq.LANE_BUCKETS[-1]
        rng = np.random.default_rng(9)
        c = rng.integers(0, 256, (2, cap + 12345), dtype=np.uint8)
        out = await q.apply(mat, c)
        assert np.array_equal(out, gf256.host_apply(mat, c))
        d = q.perf.dump()
        assert d["device_launches"] == 2 and d["host_requests"] == 0
        await q.stop()
    asyncio.run(run())


def test_device_lanes_launched_counts_the_bucket_of_every_launch():
    """What was LAUNCHED against what was asked for: each device_call
    runs a whole lane bucket, and `device_bytes` / k are the lanes the
    requests brought; the difference is padding."""
    from ceph_tpu.osd import ec_queue as eq

    async def run():
        q = make_queue(min_device_bytes=0)
        mat = gen_mat(2, 1)
        rng = np.random.default_rng(3)
        small = [rng.integers(0, 256, (2, 512), dtype=np.uint8)
                 for _ in range(5)]
        # a lone record-sized request: 512 lanes asked, a bucket launched
        out = await q.apply(mat, small[0])
        assert np.array_equal(out, gf256.host_apply(mat, small[0]))
        d = q.perf.dump()
        assert d["host_requests"] == 0 and d["device_launches"] == 1
        assert d["device_lanes_launched"] == eq.LANE_BUCKETS[0] == 16384
        assert d["device_bytes"] // 2 == 512
        # five folded into one launch still launch ONE smallest bucket
        await asyncio.gather(*[q.apply(mat, c) for c in small])
        d = q.perf.dump()
        assert d["device_launches"] == 2
        assert d["device_lanes_launched"] == 2 * 16384
        assert d["device_bytes"] // 2 == 6 * 512
        pad = 1 - (d["device_bytes"] / 2) / d["device_lanes_launched"]
        assert pad == pytest.approx(1 - 3072 / 32768)
        # one lane over a bucket launches the next; beyond the largest
        # it splits into two windows, each its own bucket
        await q.apply(mat, rng.integers(0, 256, (2, 16385), dtype=np.uint8))
        assert q.perf.dump()["device_lanes_launched"] == \
            2 * 16384 + eq.LANE_BUCKETS[1]
        before = q.perf.dump()["device_lanes_launched"]
        cap = eq.LANE_BUCKETS[-1]
        await q.apply(mat, rng.integers(0, 256, (2, cap + 100),
                                        dtype=np.uint8))
        d = q.perf.dump()
        assert d["device_lanes_launched"] - before == cap + 16384
        assert d["host_bytes"] == 0
        await q.stop()
    asyncio.run(run())


def test_mode_on_without_accelerator_fails_the_start():
    """mode=on REQUIRES a real accelerator: the backend is resolved
    once, before the queue takes requests, and on the CPU jax backend
    that resolution raises — `on` never quietly means "host"."""
    q = make_queue(mode="on")
    with pytest.raises(RuntimeError, match="requires an accelerator"):
        q.resolve_backend()


def test_osd_start_fails_with_mode_on_and_no_accelerator():
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from test_osd import Cluster, FAST_CFG
    saved = dict(FAST_CFG)
    FAST_CFG["osd_ec_batch_device"] = "on"
    try:
        async def run():
            cl = Cluster()
            try:
                with pytest.raises(RuntimeError,
                                   match="requires an accelerator"):
                    await cl.start(1)
            finally:
                await cl.stop()
        asyncio.run(run())
    finally:
        FAST_CFG.clear()
        FAST_CFG.update(saved)


def test_mode_auto_takes_the_host_on_cpu_backend():
    """auto decides at start by the backend it observes: on the CPU
    jax backend every request goes to the native host kernel."""
    async def run():
        q = make_queue(mode="auto", min_device_bytes=256)
        assert q.resolve_backend() is False
        mat = gen_mat()
        c = np.arange(4 * (1 << 17), dtype=np.uint8).reshape(4, -1) \
            .astype(np.uint8)
        out = await q.apply(mat, c)
        assert np.array_equal(out, gf256.host_apply(mat, c))
        d = q.perf.dump()
        assert d["host_requests"] == 1 and d["device_requests"] == 0
        await q.stop()
    asyncio.run(run())


def test_bytes_quorum_flushes_before_window():
    """A batch that reaches flush_bytes must launch immediately instead
    of sitting out the full fill window."""
    import time

    async def run():
        q = make_queue(window_ms=500.0, min_device_bytes=256)
        q.flush_bytes = 1 << 12
        mat = gen_mat()
        c = np.arange(4 * (1 << 14), dtype=np.uint8).reshape(4, -1) \
            .astype(np.uint8)
        t0 = time.perf_counter()
        out = await q.apply(mat, c)
        dt = time.perf_counter() - t0
        assert np.array_equal(out, gf256.host_apply(mat, c))
        assert q.perf.dump()["device_requests"] == 1
        assert dt < 0.4, f"quorum flush took {dt:.3f}s (window stall)"
        await q.stop()
    asyncio.run(run())


def test_mode_off_never_touches_device():
    async def run():
        q = make_queue(mode="off")
        mat = gen_mat()
        c = np.arange(4 * 100000, dtype=np.uint8).reshape(4, -1) & 0xFF
        c = c.astype(np.uint8)
        out = await q.apply(mat, c)
        assert np.array_equal(out, gf256.host_apply(mat, c))
        assert q.perf.dump()["device_requests"] == 0
        await q.stop()
    asyncio.run(run())


def test_device_failure_falls_back_to_host(monkeypatch):
    async def run():
        q = make_queue(min_device_bytes=256)

        def boom(reqs):
            raise RuntimeError("device gone")
        monkeypatch.setattr(q, "_run_group", boom)
        mat = gen_mat()
        c = np.arange(4 * (1 << 17), dtype=np.uint8).reshape(4, -1) \
            .astype(np.uint8)
        out = await q.apply(mat, c)
        assert np.array_equal(out, gf256.host_apply(mat, c))
        d = q.perf.dump()
        assert d["host_requests"] == 1
        # the reroute is counted, never silent
        assert d["device_fallbacks"] == 1 and d["device_bytes"] == 0
        await q.stop()
    asyncio.run(run())


# ------------------------------------------------- requests as rows

def _rows_of(arr):
    """k read-only 1-D rows, each a view of its own bytes: a decode's
    survivors as the shard replies bring them."""
    return [np.frombuffer(row.tobytes(), np.uint8) for row in arr]


@pytest.mark.parametrize("grouping", ["lone", "mixed_group"])
@pytest.mark.parametrize("mode", ["force", "off"])
def test_rows_request_equals_the_stacked_array(mode, grouping):
    """apply(mat, rows) is apply(mat, np.stack(rows)) byte for byte, on
    the device path and on the host kernel, alone or in one group with
    an array request under the same matrix; only the rows request is
    counted as one."""
    async def run():
        q = make_queue(mode=mode, min_device_bytes=256)
        mat = gen_mat()
        rng = np.random.default_rng(31)
        src = rng.integers(0, 256, (4, 5000), dtype=np.uint8)
        rows = _rows_of(src)
        if grouping == "lone":
            got = await q.apply(mat, rows)
        else:
            other = rng.integers(0, 256, (4, 3000), dtype=np.uint8)
            got, got_other = await asyncio.gather(q.apply(mat, rows),
                                                  q.apply(mat, other))
            assert np.array_equal(got_other, gf256.host_apply(mat, other))
        assert np.array_equal(got, await q.apply(mat, np.stack(rows)))
        assert np.array_equal(got, gf256.host_apply(mat, src))
        d = q.perf.dump()
        assert d["row_requests"] == 1
        assert d["device_fallbacks"] == 0
        if mode == "force":
            assert d["host_bytes"] == 0
            assert d["device_launches"] == 2
            assert d["device_requests"] == (2 if grouping == "lone" else 3)
        else:
            assert d["device_requests"] == 0
        await q.stop()
    asyncio.run(run())


def test_rows_request_after_a_device_failure_reroutes_whole(monkeypatch):
    """The device-failure fallback stacks a rows request for the host
    kernel: the same bytes come back, and the reroute is counted."""
    async def run():
        q = make_queue(min_device_bytes=256)

        def boom(reqs):
            raise RuntimeError("device gone")
        monkeypatch.setattr(q, "_run_group", boom)
        mat = gen_mat()
        src = np.random.default_rng(32).integers(0, 256, (4, 1 << 15),
                                                 dtype=np.uint8)
        out = await q.apply(mat, _rows_of(src))
        assert np.array_equal(out, gf256.host_apply(mat, src))
        d = q.perf.dump()
        assert d["device_fallbacks"] == 1 and d["row_requests"] == 1
        assert d["host_bytes"] == 4 * (1 << 15)
        await q.stop()
    asyncio.run(run())


# ------------------------------------------------------ continuations

def _digest_on(seen):
    """A continuation that says where and how often it ran, keeps a
    copy of its rows and returns something that is not the rows."""
    import threading

    def finish(chunks, rows):
        seen.append(threading.current_thread().name)
        return ("done", chunks.shape[1], rows.tobytes())
    return finish


def test_continuation_runs_on_the_device_thread_once_per_request():
    """mode=force: each request's continuation runs on the ec-device
    thread, once, with the request's own chunks and its own rows of
    the group's result, and the await resolves to what it returned."""
    async def run():
        q = make_queue(min_device_bytes=256)
        mat = gen_mat()
        rng = np.random.default_rng(21)
        ins = [rng.integers(0, 256, (4, 2048 + 128 * i), dtype=np.uint8)
               for i in range(5)]
        seen = []
        outs = await asyncio.gather(
            *[q.apply_then(mat, c, _digest_on(seen)) for c in ins])
        for c, o in zip(ins, outs):
            assert o == ("done", c.shape[1],
                         gf256.host_apply(mat, c).tobytes())
        assert len(seen) == 5
        assert all(name.startswith("ec-device") for name in seen), seen
        d = q.perf.dump()
        assert d["device_requests"] == 5 and d["device_launches"] == 1
        assert d["finish_thread"] == 5 and d["finish_inline"] == 0
        assert d["host_requests"] == 0 and d["device_fallbacks"] == 0
        await q.stop()
    asyncio.run(run())


def test_requests_without_a_continuation_are_as_before_beside_one():
    """One group, requests with and without `finish`: the plain ones
    resolve to their own contiguous copy of the rows, byte for byte the
    host kernel's; only the others are counted."""
    async def run():
        q = make_queue(min_device_bytes=256)
        mat = gen_mat()
        rng = np.random.default_rng(22)
        ins = [rng.integers(0, 256, (4, 4096), dtype=np.uint8)
               for _ in range(6)]
        seen = []
        outs = await asyncio.gather(
            *[q.apply_then(mat, c, _digest_on(seen)) if i % 2
              else q.apply(mat, c) for i, c in enumerate(ins)])
        for i, (c, o) in enumerate(zip(ins, outs)):
            want = gf256.host_apply(mat, c)
            if i % 2:
                assert o == ("done", 4096, want.tobytes())
            else:
                assert isinstance(o, np.ndarray) and o.dtype == np.uint8
                assert o.flags.c_contiguous and np.array_equal(o, want)
        d = q.perf.dump()
        assert d["device_launches"] == 1 and d["device_requests"] == 6
        assert d["finish_thread"] == 3 and d["finish_inline"] == 0
        await q.stop()
    asyncio.run(run())


@pytest.mark.parametrize("path", ["mode_off", "auto_on_cpu", "small_lone"])
def test_continuation_runs_inline_on_the_host_kernel_path(path):
    """Where the result is made on the caller's thread (the host
    kernel), the continuation runs there, in the caller's step."""
    import threading

    async def run():
        q = make_queue(mode={"mode_off": "off", "auto_on_cpu": "auto",
                             "small_lone": "force"}[path],
                       min_device_bytes=1 << 20 if path == "small_lone"
                       else 256)
        mat = gen_mat()
        c = np.arange(4 * 4096, dtype=np.uint32).astype(np.uint8) \
            .reshape(4, -1)
        seen = []
        out = await q.apply_then(mat, c, _digest_on(seen))
        assert out == ("done", 4096, gf256.host_apply(mat, c).tobytes())
        assert seen == [threading.current_thread().name]
        d = q.perf.dump()
        assert d["host_requests"] == 1 and d["device_requests"] == 0
        assert d["finish_inline"] == 1 and d["finish_thread"] == 0
        assert d["device_fallbacks"] == 0
        await q.stop()
    asyncio.run(run())


def test_continuation_runs_inline_after_a_device_failure(monkeypatch):
    """The device-failure fallback makes the result on the loop, so the
    continuation runs there too; the only fallback counted is the
    injected failure."""
    import threading

    async def run():
        q = make_queue(min_device_bytes=256)

        def boom(reqs):
            raise RuntimeError("device gone")
        monkeypatch.setattr(q, "_run_group", boom)
        mat = gen_mat()
        rng = np.random.default_rng(23)
        ins = [rng.integers(0, 256, (4, 1 << 15), dtype=np.uint8)
               for _ in range(3)]
        seen = []
        outs = await asyncio.gather(
            *[q.apply_then(mat, c, _digest_on(seen)) for c in ins])
        for c, o in zip(ins, outs):
            assert o == ("done", 1 << 15,
                         gf256.host_apply(mat, c).tobytes())
        assert seen == [threading.current_thread().name] * 3
        d = q.perf.dump()
        assert d["device_fallbacks"] == 1 and d["device_bytes"] == 0
        assert d["host_requests"] == 3
        assert d["finish_inline"] == 3 and d["finish_thread"] == 0
        await q.stop()
    asyncio.run(run())


@pytest.mark.parametrize("path", ["device_thread", "host_kernel"])
def test_raising_continuation_fails_its_own_request_only(path):
    """What a continuation raises is its request's error: the rest of
    the group completes, nothing is booked as a device fallback and
    nothing is run again on the host."""
    class Boom(Exception):
        pass

    async def run():
        q = make_queue(mode="force" if path == "device_thread" else "off",
                       min_device_bytes=256)
        mat = gen_mat()
        rng = np.random.default_rng(24)
        ins = [rng.integers(0, 256, (4, 4096), dtype=np.uint8)
               for _ in range(4)]
        seen = []

        def bad(chunks, rows):
            raise Boom("mine alone")

        outs = await asyncio.gather(
            *[q.apply_then(mat, c, bad if i == 1 else _digest_on(seen))
              for i, c in enumerate(ins)], return_exceptions=True)
        assert isinstance(outs[1], Boom)
        for i in (0, 2, 3):
            assert outs[i] == ("done", 4096,
                               gf256.host_apply(mat, ins[i]).tobytes())
        assert len(seen) == 3
        d = q.perf.dump()
        assert d["device_fallbacks"] == 0
        if path == "device_thread":
            assert d["device_requests"] == 4 and d["host_requests"] == 0
            assert d["finish_thread"] == 4
        else:
            assert d["host_requests"] == 4 and d["finish_inline"] == 4
        # the queue is whole: the next request goes through
        c = ins[0]
        assert np.array_equal(await q.apply(mat, c),
                              gf256.host_apply(mat, c))
        await q.stop()
    asyncio.run(run())


def test_rows_replaced_in_front_of_apply_lose_what_was_made_of_them():
    """The rows come back through apply(), so a wrapper around it (the
    benchmark's `seam_corrupt` control is one) sees them and may hand
    on others.  Only the very rows the seam returned carry the
    continuation's product: replaced rows get the continuation again,
    inline, so what the caller stores is made of what came back."""
    async def run():
        q = make_queue(min_device_bytes=256)
        real = q.apply

        async def corrupting(mat, chunks):
            out = (await real(mat, chunks)).copy()
            out[0, 0] ^= 0x01
            return out
        q.apply = corrupting
        mat = gen_mat()
        c = np.random.default_rng(25).integers(0, 256, (4, 8192),
                                               dtype=np.uint8)
        seen = []
        out = await q.apply_then(mat, c, _digest_on(seen))
        want = gf256.host_apply(mat, c)
        want[0, 0] ^= 0x01
        assert out == ("done", 8192, want.tobytes())
        assert len(seen) == 2 and seen[0].startswith("ec-device")
        d = q.perf.dump()
        assert d["finish_thread"] == 1 and d["finish_inline"] == 1
        await q.stop()
    asyncio.run(run())


# -------------------------------------------- op tracing at the seam

EXEC_SECTIONS = ("seam_fold", "seam_h2d", "seam_launch", "seam_d2h")
#: the sections that run once per launch window, not once per group
WINDOW_SECTIONS = ("seam_h2d", "seam_launch")


def _seam_sums(q):
    return {name: (h.count, h.sum) for name, h in
            q.ctx.tracer.hist.histograms().items()
            if name.startswith("seam_")}


@pytest.mark.parametrize("last", ["seam_split", "seam_finish"])
@pytest.mark.parametrize("grouping", ["own_groups", "one_group"])
def test_seam_stages_tile_the_apply_await(grouping, last):
    _check_seam_stages_tile(grouping, last, as_rows=False)


@pytest.mark.parametrize("last", ["seam_split", "seam_finish"])
@pytest.mark.parametrize("grouping", ["own_groups", "one_group"])
def test_seam_stages_tile_the_apply_await_of_rows(grouping, last):
    """The same account for requests handed in as rows (a decode's
    survivors): the fold copies them, and the stages still tile."""
    _check_seam_stages_tile(grouping, last, as_rows=True)


def _check_seam_stages_tile(grouping, last, as_rows):
    """With op_tracing on, a device request's trip is tiled by
    seam_pending (enqueue -> the executor takes its group), the five
    sections on the ec-device thread, and seam_resume (the executor's
    last instant -> the awaiter runs again): together within 10% of
    seam_apply.  Sections are per GROUP, but for seam_h2d and
    seam_launch, which run once per launch window of it (the interval
    seam_window spans the two): when n requests share one group each
    of them waits through the same sections, so against the
    per-request intervals they weigh n.  The fifth section is
    seam_split (the result copies) for requests without a continuation
    and seam_finish (the continuations, which take their rows as
    views) for requests with one: a group records the one it has work
    for."""
    n = 6
    sections = EXEC_SECTIONS + (last,)
    other = "seam_finish" if last == "seam_split" else "seam_split"

    def finish(chunks, rows):
        return np.ascontiguousarray(rows)

    async def run():
        q = make_queue(min_device_bytes=256, window_ms=2.0)
        q.ctx.config.set("op_tracing", True)
        rng = np.random.default_rng(11)
        if grouping == "own_groups":
            # distinct matrices: one group, one launch per request,
            # each waiting behind the groups launched before it
            mats = [rng.integers(1, 256, (2, 4), dtype=np.uint8)
                    for _ in range(n)]
        else:
            mats = [gen_mat()] * n
        ins = [rng.integers(0, 256, (4, 1 << 18), dtype=np.uint8)
               for _ in range(n)]
        given = [_rows_of(c) for c in ins] if as_rows else ins

        async def burst():
            outs = await asyncio.gather(
                *[q.apply_then(m, c, finish) if last == "seam_finish"
                  else q.apply(m, c) for m, c in zip(mats, given)])
            for m, c, o in zip(mats, ins, outs):
                assert np.array_equal(o, gf256.host_apply(m, c))

        await burst()                      # compiles, imports
        before = _seam_sums(q)
        launches = q.perf.dump()["device_launches"]
        fills = q.perf.dump()["batch_fill"]["avgcount"]
        await burst()
        after = _seam_sums(q)
        d = {k: (after[k][0] - before[k][0], after[k][1] - before[k][1])
             for k in after}
        groups = q.perf.dump()["batch_fill"]["avgcount"] - fills
        windows = q.perf.dump()["device_launches"] - launches
        assert groups == (n if grouping == "own_groups" else 1)
        # the one group of 6 x 2^18 lanes is one window of 2^22
        assert windows == groups
        for name in ("seam_apply", "seam_pending", "seam_resume"):
            assert d[name][0] == n, (name, d[name])
        for name in sections:
            want = windows if name in WINDOW_SECTIONS else groups
            assert d[name][0] == want, (name, d[name])
        assert d["seam_window"][0] == windows, d["seam_window"]
        assert d["seam_window"][1] >= d["seam_h2d"][1] + d["seam_launch"][1]
        assert other not in d, d
        weight = n // groups
        tiled = d["seam_pending"][1] + d["seam_resume"][1] \
            + weight * sum(d[name][1] for name in sections)
        total = d["seam_apply"][1]
        assert abs(tiled - total) <= 0.10 * total, (tiled, total, d)
        assert q.perf.dump()["row_requests"] == (2 * n if as_rows else 0)
        await q.stop()
    asyncio.run(run())


def test_lone_small_request_on_the_host_kernel_records_seam_apply_only():
    async def run():
        q = make_queue(min_device_bytes=1 << 20)
        q.ctx.config.set("op_tracing", True)
        mat = gen_mat()
        c = np.arange(4 * 512, dtype=np.uint8).reshape(4, 512)
        out = await q.apply(mat, c)
        assert np.array_equal(out, gf256.host_apply(mat, c))
        assert q.perf.dump()["host_requests"] == 1
        sums = _seam_sums(q)
        assert set(sums) == {"seam_apply"} and sums["seam_apply"][0] == 1
        await q.stop()
    asyncio.run(run())


def test_inline_continuation_is_loop_time_with_a_name():
    """On the host-kernel path the continuation is EC host work on the
    caller's loop: one `loop_ec_host` section, no `seam_finish`."""
    async def run():
        q = make_queue(mode="off")
        q.ctx.config.set("op_tracing", True)
        c = np.arange(4 * 512, dtype=np.uint8).reshape(4, 512)
        out = await q.apply_then(gen_mat(), c, lambda ch, rows: 7)
        assert out == 7
        assert set(_seam_sums(q)) == {"seam_apply"}
        assert q.ctx.tracer.hist.histograms()["loop_ec_host"].count == 1
        await q.stop()
    asyncio.run(run())


def test_seam_records_nothing_with_tracing_off():
    from ceph_tpu.common.tracer import STAGE_GROUP

    async def run():
        q = make_queue(min_device_bytes=256)
        c = np.arange(4 * 4096, dtype=np.uint8).reshape(4, -1) \
            .astype(np.uint8)
        await asyncio.gather(q.apply(gen_mat(), c), q.apply(gen_mat(), c))
        assert q.perf.dump()["device_requests"] == 2
        assert STAGE_GROUP not in q.ctx.perf._groups
        await q.stop()
    asyncio.run(run())


def test_ec_pool_writes_ride_the_device_queue():
    """E2E: cluster with osd_ec_batch_device=force — concurrent EC writes
    coalesce on the primary's device queue and read back intact."""
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    from test_osd import Cluster, FAST_CFG
    saved = dict(FAST_CFG)
    FAST_CFG["osd_ec_batch_device"] = "force"
    FAST_CFG["osd_ec_batch_min_bytes"] = 1024
    try:
        async def run():
            cl = Cluster()
            admin = await cl.start(6)
            await admin.pool_create("ecpool", pg_num=8,
                                    pool_type="erasure", k=4, m=2)
            io = admin.open_ioctx("ecpool")
            rng = np.random.default_rng(3)
            payloads = {f"obj{i}": rng.integers(
                0, 256, 16384 + 512 * i, dtype=np.uint8).tobytes()
                for i in range(6)}
            await asyncio.gather(*[io.write_full(k, v)
                                   for k, v in payloads.items()])
            for k, v in payloads.items():
                assert await io.read(k) == v
            stats = [osd.ec_queue.perf.dump() for osd in cl.osds.values()]
            total_dev = sum(s["device_bytes"] for s in stats)
            total_reqs = sum(s["device_requests"] for s in stats)
            launches = sum(s["device_launches"] for s in stats)
            assert total_reqs == len(payloads)
            assert total_dev > 0
            assert launches <= total_reqs     # coalescing may merge them
            await cl.stop()
        asyncio.run(run())
    finally:
        FAST_CFG.clear()
        FAST_CFG.update(saved)
