"""blob_copies (B/B): blob payload bytes that took a copy between the
receive slab and their consumer, per blob payload byte delivered, over
the window (`decoder.blob.copied.bytes` over `decoder.blob.bytes`).  0
where the digest path gets views of the slabs (a private pipeline), 1
where each blob is joined once (behind the hub).  None where the
program has no such counters or no blob byte moved in the window."""

import _stats


def read(ctx):
    snaps = _stats.pair(ctx)
    if snaps is None:
        return None
    last = snaps[1]["metrics"]["counters"]
    if "decoder.blob.copied.bytes" not in last \
            or "decoder.blob.bytes" not in last:
        return None
    moved = _stats.counter_delta(ctx, "decoder.blob.bytes")
    if moved <= 0:
        return None
    return _stats.counter_delta(ctx, "decoder.blob.copied.bytes") / moved
