"""Process environment: which process gets the chip, and where the
process that has it keeps compiled code.

A TPU chip belongs to ONE process at a time: a process that has
initialised a non-CPU jax backend holds the chip, and any other
process that asks for it fails or hangs.  So the on-chip deployment
shape is the single-process cluster (``qa.cluster.Cluster``: mon +
OSDs + client on one loop), and every helper process a chip owner
could spawn — vstart's per-daemon children, process-lane workers —
runs jax on the CPU.

This module never imports jax at module scope: jax-free parents
(vstart, the bench orchestrator) import it.
"""

from __future__ import annotations

import os
import pathlib
from typing import Dict, Optional

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cpu_child_env(extra: Optional[Dict[str, str]] = None,
                  pythonpath_first: str = "") -> Dict[str, str]:
    """Environment for a python child that must stay off the chip:
    JAX_PLATFORMS forced to cpu (unless the caller overrides)."""
    env = dict(os.environ)
    if pythonpath_first:
        env["PYTHONPATH"] = ":".join(
            p for p in (pythonpath_first, env.get("PYTHONPATH", "")) if p)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


def pinned_to_cpu() -> bool:
    """True when this process's environment holds jax to the CPU."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() \
        .startswith("cpu")


def accelerator_present() -> bool:
    """True when this process's default jax backend is a real
    accelerator.  A process pinned to the CPU answers without paying
    the jax import."""
    if pinned_to_cpu():
        return False
    import jax
    return jax.default_backend() != "cpu"


def compile_cache_dir() -> str:
    """Where jax's persistent compilation cache lives: wherever
    JAX_COMPILATION_CACHE_DIR says, otherwise a FIXED directory in the
    checkout — a directory that moves (temp name, pid, clock) never
    hits."""
    return os.environ.get(_CACHE_ENV) or str(_CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process and
    return its directory.  Called by every process that owns the
    device (chip_smoke.py, bench.py stages, an OSD whose EC queue
    resolved a device backend) BEFORE its first compile.  With
    JAX_COMPILATION_CACHE_DIR set jax already points there and no
    other directory is set in code.  The thresholds drop to zero so
    the small kernels (each EC lane bucket compiles in well under
    jax's default 1 s floor) are kept too."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(_CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
