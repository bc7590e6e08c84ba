"""An EC read's bytes on the primary's loop (osd/backend.py).

Between the shard replies and the client's bytes the read copies its
data once: the reply joins the k data rows where they lie (reply views,
decoded rows), and a degraded read hands its survivors to the seam as
rows (`ECBatchQueue.apply`), which folds them on the ec-device thread.
Driven on a live in-process k=4 m=2 cluster with one OSD down (down,
not out: no recovery moves a shard while the reads run) and the seam's
device path forced on the CPU jax backend.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.qa.cluster import FAST_CFG, Cluster


@pytest.fixture
def device_seam_cfg():
    saved = dict(FAST_CFG)
    FAST_CFG["osd_ec_batch_device"] = "force"
    # every request, a lone decode too, takes the device path: only the
    # host kernel stacks rows
    FAST_CFG["osd_ec_batch_min_bytes"] = 0
    FAST_CFG["mon_osd_down_out_interval"] = 3600.0
    try:
        yield
    finally:
        FAST_CFG.clear()
        FAST_CFG.update(saved)


def _seam(cl, key):
    return sum(osd.ec_queue.perf.dump()[key] for osd in cl.osds.values())


def test_degraded_reads_copy_once_and_stack_nothing(device_seam_cfg,
                                                    monkeypatch):
    async def run():
        cl = Cluster()
        admin = await cl.start(6)
        try:
            await admin.pool_create("ecpool", pg_num=16,
                                    pool_type="erasure", k=4, m=2)
            io = admin.open_ioctx("ecpool")
            rng = np.random.default_rng(38)
            # whole stripes and a ragged tail (the read slices the
            # padded stripe back to the object's size)
            payloads = {f"obj{i}": rng.integers(
                0, 256, 16384 + 1000 * i, dtype=np.uint8).tobytes()
                for i in range(16)}
            await cl.write_burst(io, payloads)
            # a write's encode reaches the seam as one array
            assert _seam(cl, "device_requests") >= len(payloads)
            assert _seam(cl, "row_requests") == 0

            victim = 2
            await cl.kill_osd(victim)
            await cl.mark_down_and_wait(admin, victim)

            stacks = []
            real_stack = np.stack

            def counting_stack(*a, **kw):
                stacks.append(1)
                return real_stack(*a, **kw)
            monkeypatch.setattr(np, "stack", counting_stack)
            # the first pass meets the new interval (peering, the
            # clients' new map); the second is counted read by read
            for name, data in payloads.items():
                assert await io.read(name) == data
            decoded, whole = [], []
            for name, data in payloads.items():
                before = {key: _seam(cl, key)
                          for key in ("device_requests", "row_requests")}
                assert await io.read(name) == data
                seam = {key: _seam(cl, key) - n for key, n in before.items()}
                # one decode, handed in as rows, or none at all
                assert seam["device_requests"] in (0, 1), (name, seam)
                assert seam["row_requests"] == seam["device_requests"], \
                    (name, seam)
                (decoded if seam["row_requests"] else whole).append(name)
            monkeypatch.setattr(np, "stack", real_stack)
            assert stacks == []
            # the victim held a data shard of some objects' PGs and a
            # parity shard of the others': both kinds were read
            assert decoded and whole, (decoded, whole)
            assert _seam(cl, "host_bytes") == 0
            assert _seam(cl, "device_fallbacks") == 0
        finally:
            await cl.stop()
    asyncio.run(run())
