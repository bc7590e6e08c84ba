"""Per-layer metric `osd.loop_idle_share.op_rate`: the loop sampler's
evloop_idle over loop_wall: percent of the one Python loop's time asleep
in `select` with nothing ready."""

from benchmark import loop_account


def read(obs):
    return loop_account.loop_idle_share(obs)
