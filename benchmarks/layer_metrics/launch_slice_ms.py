"""launch_slice_ms (ms): host milliseconds a bucket dispatch spends in
its two result slices, `hh[:n]` and `hl[:n]` — a jax program each, on
the one-chip arm only (`span.digest.launch.slice.seconds`: the window's
sum over its count).  None over a mesh, which cuts on the host, and
where the program does not split `digest.launch`."""

import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "span.digest.launch.slice.seconds")
