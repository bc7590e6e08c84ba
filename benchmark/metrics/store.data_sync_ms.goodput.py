"""Per-layer metric `store.data_sync_ms.goodput`: tracer section
store_data_sync on the kv-sync threads (the one fsync of the block file
a commit group issues), mean ms per group."""


def read(obs):
    n, secs = obs.stages.get("store_data_sync", (0, 0.0))
    return secs * 1e3 / n if n else None
