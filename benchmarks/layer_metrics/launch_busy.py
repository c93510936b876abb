"""launch_busy (s/s): host seconds per wall second launching the hash
program of a bucket — the jit call, `jnp.asarray(lengths)` and the two
result slices (`span.digest.launch.seconds`)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "span.digest.launch.seconds")
