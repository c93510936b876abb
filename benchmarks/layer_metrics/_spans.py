"""What the stage readers share: one registry histogram of the sidecar
(`span.<name>.seconds` of a stage span, a queue clock, a loop phase)
across the window — its sum and count in the last `--stats-fd` snapshot
minus the first.  A program that has no such histogram (the parent of
the PR that added the stage spans, a cell whose path never opens the
span) reads None, and the metric is left out of the line.

Not a metric: the underscore keeps it out of the names run.py resolves.
"""

from __future__ import annotations

import _stats


def delta(ctx, name: str):
    """(seconds added, observations added) over the window, or None."""
    snaps = _stats.pair(ctx)
    if snaps is None:
        return None
    first, last = (s["metrics"]["histograms"].get(name) for s in snaps)
    if last is None:
        return None
    if first is None:       # first lit use fell inside the window
        first = {"sum": 0.0, "count": 0}
    return last["sum"] - first["sum"], last["count"] - first["count"]


def busy(ctx, name: str):
    """Seconds inside the stage per wall second (s/s).  Summed over the
    threads that run it, so it can pass 1.0 where sessions run side by
    side."""
    d = delta(ctx, name)
    if d is None:
        return None
    return d[0] / _stats.seconds(ctx)


def mean_ms(ctx, name: str):
    """Mean of the window's observations, in ms; None for none."""
    d = delta(ctx, name)
    if d is None or d[1] <= 0:
        return None
    return 1e3 * d[0] / d[1]
