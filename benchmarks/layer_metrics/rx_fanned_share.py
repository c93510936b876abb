"""rx_fanned_share (%): of the bytes the edge loop's read phase received
over the window, the share whose receive ran on a helper thread beside
its neighbours' (`edge.rx.fanned.bytes` over `edge.rx.bytes`).  High
where several sessions stream bulk at once, near 0 on a feed of small
reads.  None where the program has no such counters or the loop
received nothing in the window."""

import _stats


def read(ctx):
    snaps = _stats.pair(ctx)
    if snaps is None:
        return None
    last = snaps[1]["metrics"]["counters"]
    if "edge.rx.fanned.bytes" not in last or "edge.rx.bytes" not in last:
        return None
    received = _stats.counter_delta(ctx, "edge.rx.bytes")
    if received <= 0:
        return None
    return 100.0 * _stats.counter_delta(ctx, "edge.rx.fanned.bytes") \
        / received
