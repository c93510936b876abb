"""kernel_pad_share (%): the share of the bytes staged for the device
that is padding, from the program's own count of the payload:
1 - payload_bytes / (padded_items x nblocks x 128) over the window's
`blake2b_buckets` rows.  Both paddings count, as in `pad_share`; where
the clients send blobs the host stream hashes (`plain.objects`), this
one leaves them out and `pad_share` counts them as staged payload."""

import _kernel_bytes

BLOCK = 128


def read(ctx):
    rows = _kernel_bytes.bucket_deltas(ctx)
    if rows is None:
        return None
    staged = sum(r["padded_items"] * int(key.rsplit(":", 1)[1]) * BLOCK
                 for key, r in rows.items())
    return 100.0 * (1.0 - sum(r["payload_bytes"] for r in rows.values())
                    / staged)
