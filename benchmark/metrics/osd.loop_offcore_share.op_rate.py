"""Per-layer metric `osd.loop_offcore_share.op_rate`: loop_wall less
evloop_idle less loop_cpu, over loop_wall: percent of the one Python
loop's time in which it was runnable and not on a core (the GIL, or the
kernel's scheduler).  With osd.loop_cpu_share and osd.loop_idle_share it
sums to 100."""

from benchmark import loop_account


def read(obs):
    return loop_account.loop_offcore_share(obs)
