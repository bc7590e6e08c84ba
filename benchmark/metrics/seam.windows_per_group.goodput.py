"""Per-layer metric `seam.windows_per_group.goodput`: launch windows per
device group, the count of the tracer interval seam_window (one per
window) over that of the section seam_fold (one per group)."""


def read(obs):
    windows = obs.stages.get("seam_window", (0, 0.0))[0]
    groups = obs.stages.get("seam_fold", (0, 0.0))[0]
    return windows / groups if windows and groups else None
