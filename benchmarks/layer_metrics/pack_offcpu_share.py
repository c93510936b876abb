"""pack_offcpu_share (%): of the dispatching thread's seconds inside
`digest.pack`, the share it was off its CPU.  The pack copies and fills
and makes no blocking runtime call, so this is the wait for the
interpreter lock alone: the instrument's zero for the other shares
(`span.digest.pack.seconds` beside `.cpu_seconds`)."""

import _shares


def read(ctx):
    return _shares.offcpu_share(ctx, "span.digest.pack.seconds",
                                "span.digest.pack.cpu_seconds")
