"""compiles_in_window (count): programs jax traced between the window's
two snapshots (`device.jit.traces`).  Should read 0: the warm-up met
every shape."""

import _stats


def read(ctx):
    return _stats.counter_delta(ctx, "device.jit.traces")
