"""Per-layer metric `ec.encode_ms.goodput`: tracer stage ec_encode
(writes), mean ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["ec_encode"])
