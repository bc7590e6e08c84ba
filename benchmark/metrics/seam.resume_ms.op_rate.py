"""Per-layer metric `seam.resume_ms.op_rate`: tracer interval seam_resume
(the executor's last instant on the group to the awaiting op running
again), ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["seam_resume"])
