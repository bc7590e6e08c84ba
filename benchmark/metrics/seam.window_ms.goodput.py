"""Per-layer metric `seam.window_ms.goodput`: tracer interval seam_window
(one launch window's staging and device_call, wall to wall) on the
ec-device thread, ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["seam_window"])
