"""Per-layer metric `seam.device_byte_fraction.goodput`: device_bytes /
(device_bytes + host_bytes) over the window, percent."""

from benchmark import readers


def read(obs):
    return readers.device_byte_fraction(obs)
