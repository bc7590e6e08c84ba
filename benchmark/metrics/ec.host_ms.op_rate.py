"""Per-layer metric `ec.host_ms.op_rate`: tracer section loop_ec_host
(split, tobytes / crc / txn build, decode glue on the loop thread), ms
per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["loop_ec_host"])
