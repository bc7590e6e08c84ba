"""Per-layer metric `osd.loop_poll_ms.op_rate`: the loop sampler's evloop_poll
(every `select(0)` between ready callbacks: the syscall plus the wait to
win the GIL back), ms per op completed in the window."""

from benchmark import loop_account


def read(obs):
    return loop_account.loop_poll_ms(obs)
