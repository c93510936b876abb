"""fanout_frame_lag_ms (ms): the program's own append-to-delivered
delay of the broadcast — mean over the window of `fanout.frame.latency`
(one observation per peer for each publish mark the peer's send
position has covered: from the source's append of a slab to the push
that handed its last byte to that peer's socket)."""

import _spans


def read(ctx):
    return _spans.mean_ms(ctx, "fanout.frame.latency")
