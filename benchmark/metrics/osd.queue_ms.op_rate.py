"""Per-layer metric `osd.queue_ms.op_rate`: tracer queue-wait stages +
admit_wait + dep_wait, mean ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, readers.QUEUE_STAGES)
