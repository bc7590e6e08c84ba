"""Per-layer metric `seam.pending_ms.goodput`: tracer interval seam_pending
(apply() enqueue to the executor taking the group), ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["seam_pending"])
