"""batch_sessions (sessions): distinct sessions a composed hub batch
drew from, mean over the window's batches (`hub.dispatch.sessions`, one
observation a batch: sum and count, last snapshot minus first)."""

import _spans


def read(ctx):
    d = _spans.delta(ctx, "hub.dispatch.sessions")
    if d is None or d[1] <= 0:
        return None
    return d[0] / d[1]
