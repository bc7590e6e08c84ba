"""Per-layer metric `kernel.ec_apply_busy.op_rate`: union of the EC
kernel's device events over the traced window, percent."""

from benchmark import readers


def read(obs):
    return readers.ec_apply_busy(obs)
