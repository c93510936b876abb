"""pad_share (%): the share of the bytes staged for the device that is
padding: 1 - payload bytes / (padded_items x nblocks x 128) over the
window's `blake2b_buckets` rows.

Payload bytes are the rows' items times the mean payload of an item as
the clients sent it, so BOTH paddings count: the batch axis (items up to
padded_items) and the block axis (a 1,035-byte record in a 16-block
bucket).  The bucket table alone (items / padded_items) sees only the
first.  With items of very different sizes in one run the mean blurs
the split between buckets, not the total."""

import _stats

BLOCK = 128


def read(ctx):
    rows = _stats.bucket_deltas(ctx)
    mean = _stats.mean_payload_bytes(ctx)
    if rows is None or mean is None:
        return None
    staged = sum(r["padded_items"] * int(key.rsplit(":", 1)[1]) * BLOCK
                 for key, r in rows.items())
    payload = mean * sum(r["items"] for r in rows.values())
    return 100.0 * (1.0 - payload / staged)
