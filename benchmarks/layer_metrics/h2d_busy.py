"""h2d_busy (s/s): host seconds inside the two `jax.device_put` calls
of a bucket per wall second (`span.digest.h2d.seconds`).  Host time
inside the calls, not the link's: a transfer that streams after
`device_put` returned is not in it."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "span.digest.h2d.seconds")
