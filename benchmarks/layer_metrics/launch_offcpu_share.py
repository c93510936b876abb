"""launch_offcpu_share (%): of the dispatching thread's seconds inside
`digest.launch`, the share it was off its CPU — waiting to take the
interpreter lock back or blocked inside the runtime
(`span.digest.launch.seconds` beside `.cpu_seconds`)."""

import _shares


def read(ctx):
    return _shares.offcpu_share(ctx, "span.digest.launch.seconds",
                                "span.digest.launch.cpu_seconds")
