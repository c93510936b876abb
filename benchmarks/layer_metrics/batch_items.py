"""batch_items (items): items per device dispatch over the window, from
`blake2b_buckets`: sum of items / sum of dispatches.  One reader for the
per-session pipelines and for the hub: both end in the same bucket
table."""

import _stats


def read(ctx):
    rows = _stats.bucket_deltas(ctx)
    if rows is None:
        return None
    return sum(r["items"] for r in rows.values()) \
        / sum(r["dispatches"] for r in rows.values())
