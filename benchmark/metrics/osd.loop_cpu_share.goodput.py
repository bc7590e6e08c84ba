"""Per-layer metric `osd.loop_cpu_share.goodput`: the loop sampler's
loop_cpu over loop_wall: percent of the one Python loop's time in which
it burned CPU."""

from benchmark import spans


def read(obs):
    return spans.loop_cpu_share(obs)
