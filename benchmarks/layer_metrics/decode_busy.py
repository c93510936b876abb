"""decode_busy (s/s): seconds inside the decoder's dispatch per wall
second — the `decoder.dispatch.seconds` histogram's sum, last snapshot
minus first.  Summed over sessions, so it can pass 1.0 where threads
decode side by side."""

import _stats


def read(ctx):
    snaps = _stats.pair(ctx)
    if snaps is None:
        return None
    sums = [s["metrics"]["histograms"].get("decoder.dispatch.seconds", {})
            .get("sum") for s in snaps]
    if None in sums:
        return None
    return (sums[1] - sums[0]) / _stats.seconds(ctx)
