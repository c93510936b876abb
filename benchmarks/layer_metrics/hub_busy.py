"""hub_busy (s/s): seconds per wall second of the hub dispatcher's
working turns, submit loop to distribute (`hub.dispatch.latency`)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "hub.dispatch.latency")
