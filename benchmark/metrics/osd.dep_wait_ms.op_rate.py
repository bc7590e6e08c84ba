"""Per-layer metric `osd.dep_wait_ms.op_rate`: tracer stage dep_wait alone
(an admitted op's wait for the ops ahead of it on its own object (osd/sequencer.py: writes exclusive, reads behind the last write): the hot object's chain),
mean ms per op completed in the window.  It lies inside `osd.queue_ms`."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["dep_wait"])
