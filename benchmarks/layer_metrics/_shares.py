"""What the off-CPU readers share: two registry histograms of ONE region
of the sidecar, its wall seconds and the CPU seconds of the thread that
ran it (`span.<name>.seconds` beside `span.<name>.cpu_seconds`, a loop
phase, a receive), across the window.  Off-CPU = wall - cpu: inside a
stage span a thread is off its CPU only while it waits for the
interpreter lock or is blocked inside the runtime (OBSERVABILITY.md).
The program takes the wall clock on every visit of a region and the CPU
clock on one visit in a few, so the two MEANS are compared, not the two
sums.

Not a metric: the underscore keeps it out of the names run.py resolves.
"""

from __future__ import annotations

import _spans


# Fewer CPU-clock observations than this in the window is no reading:
# a mean of a handful.  On the TPU machines' host the CPU clock advances
# in 10 ms ticks, so one reading is 0 or 10 ms and ONE tick moves a
# share by 100 x 10 ms / (clocked visits x mean wall) points: under one
# point for the 24 turns of 55 ms that `edgehub.feed`'s read phase
# clocks in a window, 3 for `edgehub.publish`'s 300 launches of 8 ms,
# 14 for the 4-5 launches of 15 ms that `plain.publish` clocks
# (PERF.md section 5).
MIN_CLOCKED = 16


def offcpu_share(ctx, wall: str, cpu: str):
    """100 x (1 - mean CPU seconds / mean wall seconds of the window's
    observations), in %.  None, not 0, where the program has not both
    histograms (a parent older than the second clock, a cell whose path
    never enters the region), where the wall sum did not move in the
    window, or where the CPU clock has fewer than ``MIN_CLOCKED``
    observations in it.  Never clamped: a share under 0 (mean CPU past
    mean wall) is the CPU clock's grain around a small true share, or a
    broken clock, and either is to be seen in the line."""
    w = _spans.delta(ctx, wall)
    c = _spans.delta(ctx, cpu)
    if w is None or c is None or w[1] <= 0 or w[0] <= 0 \
            or c[1] < MIN_CLOCKED:
        return None
    return 100.0 * (1.0 - (c[0] / c[1]) / (w[0] / w[1]))
