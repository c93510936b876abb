"""launch_program_ms (ms): host milliseconds a bucket dispatch spends in
the jit call of its hash program, on one chip and over a mesh
(`span.digest.launch.program.seconds`: the window's sum over its count).
A child of `digest.launch`; None where the program does not split it."""

import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "span.digest.launch.program.seconds")
