"""Per-layer metric `store.apply_ms.goodput`: tracer section
loop_store_apply (queue_transactions on the loop, at the primary and in
the shard sub-op handler: on a block store six 1 MiB pwrites, their
extent checksums, onodes and the kv stage), ms per op."""

from benchmark import readers


def read(obs):
    return readers.stage_ms_per_op(obs, ["loop_store_apply"])
