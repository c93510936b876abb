"""batch_buckets (buckets): slot-width buckets — one program launch
each — in one dispatch of the batch engine, over the window: the
`blake2b_buckets` rows' dispatches (a row's dispatches are its bucket's
launches), summed, over the dispatches of the pipeline
(`span.digest.dispatch.seconds`, one observation a dispatch).  None
where either did not move."""

import _spans
import _stats


def read(ctx):
    rows = _stats.bucket_deltas(ctx)
    d = _spans.delta(ctx, "span.digest.dispatch.seconds")
    if rows is None or d is None or d[1] <= 0:
        return None
    return sum(r["dispatches"] for r in rows.values()) / d[1]
