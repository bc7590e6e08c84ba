"""Per-layer metric `seam.stage_ms.op_rate`: tracer sections seam_fold +
seam_h2d on the ec-device thread, ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["seam_fold", "seam_h2d"])
