"""launch_lengths_ms (ms): host milliseconds a bucket dispatch spends in
`jnp.asarray(lengths)`, the launch's own host-to-device transfer, on
the one-chip arm only (`span.digest.launch.lengths.seconds`: the
window's sum over its count).  None over a mesh, which ships the
lengths with the words, and where the program does not split
`digest.launch`."""

import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "span.digest.launch.lengths.seconds")
