"""Per-layer metric `store.group_txns.goodput`: transactions per commit
group: the count of store_commit_wait intervals over the count of
store_kv_sync sections (every group syncs its kv WAL once)."""


def read(obs):
    txns = obs.stages.get("store_commit_wait", (0, 0.0))[0]
    groups = obs.stages.get("store_kv_sync", (0, 0.0))[0]
    return txns / groups if txns and groups else None
