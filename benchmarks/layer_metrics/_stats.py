"""What several readers share: differences between the two stats
snapshots at the window's ends (`--stats-fd` records of the sidecar).

Not a metric: the underscore keeps it out of the names run.py resolves.
"""

from __future__ import annotations


def pair(ctx):
    """(first, last) snapshot, or None where the run has none."""
    snaps = ctx.get("snaps")
    if not snaps or snaps[0] is None or snaps[1] is None \
            or snaps[1]["monotonic"] <= snaps[0]["monotonic"]:
        return None
    return snaps


def seconds(ctx) -> float:
    a, b = pair(ctx)
    return b["monotonic"] - a["monotonic"]


def counter_delta(ctx, name: str):
    snaps = pair(ctx)
    if snaps is None:
        return None
    a, b = (s["metrics"]["counters"].get(name, 0) for s in snaps)
    return b - a


def bucket_deltas(ctx):
    """`blake2b_buckets` over the window: {"<engine>:<nblocks>":
    {"dispatches", "items", "padded_items"}}, rows that did not move
    left out."""
    snaps = pair(ctx)
    if snaps is None:
        return None
    a, b = (s.get("blake2b_buckets") or {} for s in snaps)
    out = {}
    for key, row in b.items():
        was = a.get(key, {})
        d = {k: row.get(k, 0) - was.get(k, 0)
             for k in ("dispatches", "items", "padded_items")}
        if d["dispatches"] > 0:
            out[key] = d
    return out or None


def mean_payload_bytes(ctx):
    """Mean payload bytes of an item, from the clients' own count of
    what they sent in sessions that were wholly right."""
    w = ctx["window"]
    return w["ok_payload_bytes"] / w["ok_items"] if w["ok_items"] else None
