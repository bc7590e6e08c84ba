"""Per-layer metric `ec.read_gather_ms.op_rate`: tracer interval read_gather
(a read's first sub-read send -> k shard streams in hand at the primary:
the read's twin of replica_rtt), mean ms per op completed in the window."""

from benchmark import loop_account


def read(obs):
    return loop_account.read_gather_ms(obs)
