"""stream_busy (s/s): seconds per wall second the decoding thread
spends hashing blobs at or over the stream threshold (8 MiB by default)
on the host, one piece a slab (`span.digest.stream.seconds`): bytes
that never reach the device.  None where the program has no such span
(a parent older than it) or no stream was fed in the window."""

import _spans


def read(ctx):
    d = _spans.delta(ctx, "span.digest.stream.seconds")
    if d is None or d[1] <= 0:
        return None
    return _spans.busy(ctx, "span.digest.stream.seconds")
