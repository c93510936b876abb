"""collect_wait (s/s): seconds per wall second the host blocks fetching
a batch's digest words (`span.digest.d2h_wait.seconds`) — the one place
it legitimately waits for the device."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "span.digest.d2h_wait.seconds")
