"""Per-layer metric `store.resume_ms.goodput`: tracer interval store_resume
(the group durable on the kv-sync thread to its completion record
running on the loop: the twin of seam_resume), mean ms per transaction."""


def read(obs):
    n, secs = obs.stages.get("store_resume", (0, 0.0))
    return secs * 1e3 / n if n else None
