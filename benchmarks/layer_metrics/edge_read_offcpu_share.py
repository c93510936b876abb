"""edge_read_offcpu_share (%): of the edge loop thread's seconds in its
read phase, the share it was off its CPU — waiting for a helper's
receive, or to take the interpreter lock back after a native receive or
a feed (`edge.turn.read_s` beside `edge.turn.read_cpu_s`)."""

import _shares


def read(ctx):
    return _shares.offcpu_share(ctx, "edge.turn.read_s",
                                "edge.turn.read_cpu_s")
