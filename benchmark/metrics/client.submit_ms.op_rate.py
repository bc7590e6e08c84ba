"""Per-layer metric `client.submit_ms.op_rate`: tracer stage
client_submit, mean ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["client_submit"])
