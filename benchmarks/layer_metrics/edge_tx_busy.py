"""edge_tx_busy (s/s): seconds per wall second of the edge loop's send
phase — encoder pull and the non-blocking socket writes
(`edge.turn.tx_s`)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "edge.turn.tx_s")
