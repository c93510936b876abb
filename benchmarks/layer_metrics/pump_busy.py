"""pump_busy (s/s): seconds per wall second inside the native receive
call of the session threads — a blocking read plus the frame scan
(`span.pump.recv.seconds`).  Where the client keeps the socket full it
is the kernel's copy and the scan; where it does not, it is the wait for
the client."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "span.pump.recv.seconds")
