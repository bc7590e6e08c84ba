"""Per-layer metric `msg.deliver_ms.goodput`: tracer stages deliver +
ack_delivery, mean ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["deliver", "ack_delivery"])
