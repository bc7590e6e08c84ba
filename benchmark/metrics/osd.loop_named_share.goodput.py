"""Per-layer metric `osd.loop_named_share.goodput`: seconds in loop_*
sections over the loop sampler's loop_cpu seconds: how much of the
loop's CPU has a name."""

from benchmark import spans


def read(obs):
    return spans.loop_named_share(obs)
