"""fanout_push_kib (KiB): bytes a scatter-gather push carried, over the
window — Δ`fanout.sent.bytes` ÷ Δ`fanout.dispatch.writev` (pushes that
accepted bytes, every peer together).  Small pushes mean the
dispatcher turns over faster than the source appends; large ones that
each turn catches up on several slabs at once.  Nothing without the
counters or with no push in the window."""

import _stats


def read(ctx):
    snaps = _stats.pair(ctx)
    if snaps is None:
        return None
    last = snaps[1]["metrics"]["counters"]
    if "fanout.sent.bytes" not in last \
            or "fanout.dispatch.writev" not in last:
        return None
    pushes = _stats.counter_delta(ctx, "fanout.dispatch.writev")
    if pushes <= 0:
        return None
    return _stats.counter_delta(ctx, "fanout.sent.bytes") / pushes / 1024.0
