"""Per-layer metric `osd.loop_longest_ms.goodput`: the longest single
loop_* section among the host events of the traced sub-window."""

from benchmark import spans


def read(obs):
    return spans.loop_longest_ms(obs)
