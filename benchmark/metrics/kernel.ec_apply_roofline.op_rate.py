"""Per-layer metric `kernel.ec_apply_roofline.op_rate`: least time at
the HBM peak for the requests' own bytes over the kernel's device time,
percent."""

from benchmark import readers


def read(obs):
    return readers.ec_apply_roofline(obs)
