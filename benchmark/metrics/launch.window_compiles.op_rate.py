"""Per-layer metric `launch.window_compiles.op_rate`: jax compile events
inside the window, count."""

from benchmark import readers


def read(obs):
    return readers.window_compiles(obs)
