"""pallas_share (%): items hashed by the Pallas kernel over all items
hashed on the device in the window (`blake2b_buckets` rows `pallas:*`
against every row; the rest went through the XLA-scan form)."""

import _stats


def read(ctx):
    rows = _stats.bucket_deltas(ctx)
    if rows is None:
        return None
    return 100.0 * sum(r["items"] for k, r in rows.items()
                       if k.startswith("pallas:")) \
        / sum(r["items"] for r in rows.values())
