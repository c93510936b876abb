"""fanout_dispatch_busy (s/s): seconds per wall second of the fan-out
dispatcher's turns (`span.fanout.dispatch.seconds`): one thread
serving every subscriber with backlog a windowed scatter-gather push —
the log's slices, the native send, the server lock's round trips, the
latency marks and the log's ack.  Near 1.0 the relay is the limit.
Nothing where the program has no such span (a parent whose dispatcher
turn was an obs-ring span alone)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "span.fanout.dispatch.seconds")
