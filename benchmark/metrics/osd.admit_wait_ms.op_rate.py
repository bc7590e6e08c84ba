"""Per-layer metric `osd.admit_wait_ms.op_rate`: tracer stage admit_wait alone
(the admitter's wait for a free slot of a PG's window (osd_pg_max_inflight_ops): the hot PG's window),
mean ms per op completed in the window.  It lies inside `osd.queue_ms`."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["admit_wait"])
