"""Per-layer metric `store.kv_sync_ms.goodput`: tracer section
store_kv_sync on the kv-sync threads (the group's kv WAL append +
fsync), mean ms per group."""


def read(obs):
    n, secs = obs.stages.get("store_kv_sync", (0, 0.0))
    return secs * 1e3 / n if n else None
