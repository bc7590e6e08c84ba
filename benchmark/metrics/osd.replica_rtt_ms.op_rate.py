"""Per-layer metric `osd.replica_rtt_ms.op_rate`: tracer stage
replica_rtt (writes), mean ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["replica_rtt"])
