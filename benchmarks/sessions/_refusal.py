"""The sidecar's refusal records, named where a session meets one.

A connection the sidecar will not serve gets one JSON line and EOF in
place of its stream (`sidecar.py`, `_send_refusal`): a hub or fan-out
admission `{"ok": false, "rejected": true, ...}`, a fan-out joiner below
the retained log `{"ok": false, "snapshot_needed": true, ...}`, a
connection that sends where the broadcast's source slot is taken
`{"ok": false, "not_source": true, ...}`.

Not a session kind: the underscore keeps it out of the names
`client.py` resolves.
"""

from __future__ import annotations

import json

KINDS = ("snapshot_needed", "rejected", "not_source")


def record(head) -> str | None:
    """`refused (<kind>): <record>` where `head`, the first bytes of a
    stream, is a refusal record; else None."""
    line = bytes(head[:4096]).split(b"\n", 1)[0]
    if not line.startswith(b"{"):
        return None
    try:
        rec = json.loads(line)
    except ValueError:
        return None
    if not isinstance(rec, dict) or rec.get("ok") is not False:
        return None
    kind = next((k for k in KINDS if rec.get(k)), None)
    if kind is None:
        return None
    return f"refused ({kind}): {line.decode(errors='replace')}"
