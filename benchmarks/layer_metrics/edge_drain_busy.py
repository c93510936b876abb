"""edge_drain_busy (s/s): seconds per wall second of the edge loop's
hub-drain phase — completions popped from the hub into reply records
(`edge.turn.hub_drain_s`)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "edge.turn.hub_drain_s")
