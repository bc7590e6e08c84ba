"""Per-layer metric `osd.reply_wait_ms.op_rate`: tracer stage reply_wait
(a pipelined write's wait to REPLY in its object's submit order, cut only
when it waited), mean ms per op completed in the window; 0.0 when no op
waited.  Until PR 34 this wait was a second cut of dep_wait."""

from benchmark import loop_account


def read(obs):
    return loop_account.reply_wait_ms(obs)
