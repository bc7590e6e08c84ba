"""Per-layer metric `store.commit_wait_ms.goodput`: tracer interval
store_commit_wait (KVSyncThread.submit to the transaction's completion
record running on the loop: all that durability adds to an op), mean ms
per transaction."""


def read(obs):
    n, secs = obs.stages.get("store_commit_wait", (0, 0.0))
    return secs * 1e3 / n if n else None
