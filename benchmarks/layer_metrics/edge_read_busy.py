"""edge_read_busy (s/s): seconds per wall second of the edge loop's read
phase — recv, frame scan, decode, hub submits (`edge.turn.read_s`)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "edge.turn.read_s")
