"""Per-layer metric `seam.batch_fill.goodput`: requests per device
group: the ec_batch_queue batch_fill counter over the window."""

from benchmark import readers


def read(obs):
    return readers.batch_fill(obs)
