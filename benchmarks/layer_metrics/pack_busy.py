"""pack_busy (s/s): host seconds inside `digest.pack` per wall second —
`ops/blake2b.pack_payloads` and the batch-axis padding, per bucket
(`span.digest.pack.seconds`)."""

import _spans


def read(ctx):
    return _spans.busy(ctx, "span.digest.pack.seconds")
