"""Per-layer metric `seam.fetch_ms.op_rate`: tracer sections seam_d2h +
seam_split on the ec-device thread, ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["seam_d2h", "seam_split"])
