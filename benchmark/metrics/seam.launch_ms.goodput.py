"""Per-layer metric `seam.launch_ms.goodput`: tracer section seam_launch
(slice, pad, device_call, concatenate) on the ec-device thread, ms per
op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["seam_launch"])
