"""pump_wait (s/s): seconds per wall second the session thread of a
thread-per-connection leg waits for its read-ahead helper `pump-rx-0`
to hand back the next slab (`span.pump.wait.seconds`) — beside
`pump_busy`, the receive's own seconds on whichever thread makes it.
None where the program has no such span (a parent older than the
read-ahead) and where no wait was observed in the window: a connection
that never left its inline receive has no helper to wait for."""

import _spans


def read(ctx):
    d = _spans.delta(ctx, "span.pump.wait.seconds")
    if d is None or d[1] <= 0:
        return None
    return _spans.busy(ctx, "span.pump.wait.seconds")
