"""Per-layer metric `ec.decode_ms.goodput`: the decode_rebuild
histogram (degraded reads), mean ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["decode_rebuild"])
