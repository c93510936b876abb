"""What the readers of the kernel's own payload count share: the
window's `blake2b_buckets` rows WITH `payload_bytes`, the bytes of the
items each bucket handed the kernel (the program notes them after the
pack).  `_stats.mean_payload_bytes` is the clients' mean item, which
counts a blob the sidecar hashes on its host stream (8 MiB or more by
default) as if it were staged; this count does not.  A program that
notes no such field (a parent older than it) reads None.

Not a metric: the underscore keeps it out of the names run.py resolves.
"""

from __future__ import annotations

import _stats

FIELDS = ("dispatches", "items", "padded_items", "payload_bytes")


def bucket_deltas(ctx):
    """{"<engine>:<nblocks>": {FIELDS}} over the window, rows that did
    not move left out; None where a row lacks `payload_bytes`."""
    snaps = _stats.pair(ctx)
    if snaps is None:
        return None
    a, b = (s.get("blake2b_buckets") or {} for s in snaps)
    out = {}
    for key, row in b.items():
        if "payload_bytes" not in row:
            return None
        was = a.get(key, {})
        d = {k: row[k] - was.get(k, 0) for k in FIELDS}
        if d["dispatches"] > 0:
            out[key] = d
    return out or None
