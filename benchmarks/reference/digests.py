"""The plain reference: what a correct sidecar must answer.

Written against WIRE.md and hashlib alone.  Nothing here imports the
package under test, so a fault in its codecs or kernels cannot hide in
the yardstick.

A session is a stream of frames `varint(len(payload) + 1) | type | payload`
(type 1 = Change, a proto2 message; type 2 = blob, raw bytes).  The
sidecar answers each with ONE Change frame, in submit order per kind:

    key     "<kind>-<seq>"        kind is "blob" or "change"
    change  <seq>                 counts from 0 per kind
    from 0, to 1
    value   BLAKE2b-256 of the frame's payload bytes
    subset  "digest:<kind>"

and ends its reply stream (EOF) only after the last digest.
"""

from __future__ import annotations

import hashlib

TYPE_CHANGE = 1
TYPE_BLOB = 2
FRAME_TYPE = {"change": TYPE_CHANGE, "blob": TYPE_BLOB}


def digest(data) -> bytes:
    return hashlib.blake2b(data, digest_size=32).digest()


def uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def frame_header(payload_len: int, type_id: int) -> bytes:
    return uvarint(payload_len + 1) + bytes((type_id,))


def change_payload(key: str, change: int, frm: int, to: int,
                   value: bytes | None = None,
                   subset: str | None = None) -> bytes:
    """A proto2 Change, fields in ascending order, absent optionals
    left out (WIRE.md, "Change payload")."""
    out = bytearray()
    if subset is not None:
        s = subset.encode()
        out += b"\x0a" + uvarint(len(s)) + s
    k = key.encode()
    out += b"\x12" + uvarint(len(k)) + k
    out += b"\x18" + uvarint(change)
    out += b"\x20" + uvarint(frm)
    out += b"\x28" + uvarint(to)
    if value is not None:
        out += b"\x32" + uvarint(len(value)) + value
    return bytes(out)


def expected_reply(kind: str, seq: int, dig: bytes) -> bytes:
    """The whole reply frame for item `seq` of `kind`, canonically
    encoded.  A reply that differs byte for byte is parsed and compared
    field by field before it is called wrong (`reply_fault`)."""
    p = change_payload(f"{kind}-{seq}", seq, 0, 1, dig, f"digest:{kind}")
    return frame_header(len(p), TYPE_CHANGE) + p


def _read_uvarint(buf, pos: int):
    shift = n = 0
    while True:
        if pos >= len(buf):
            return None, pos
        b = buf[pos]
        pos += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, pos
        shift += 7
        if shift > 63:
            raise ValueError("varint over 64 bits")


def parse_frame(buf, pos: int = 0):
    """One frame at `pos`: (type, fields or raw payload, next pos), or
    None while the frame is incomplete.  Raises ValueError on bytes no
    frame can start with."""
    n, at = _read_uvarint(buf, pos)
    if n is None or at + n > len(buf):
        return None
    if n < 1:
        raise ValueError("frame of length 0")
    type_id, payload = buf[at], bytes(buf[at + 1:at + n])
    if type_id != TYPE_CHANGE:
        return type_id, payload, at + n
    fields: dict = {}
    names = {1: "subset", 2: "key", 3: "change", 4: "from", 5: "to",
             6: "value"}
    p = 0
    while p < len(payload):
        tag, p = _read_uvarint(payload, p)
        if tag is None:
            raise ValueError("truncated Change")
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            v, p = _read_uvarint(payload, p)
            if v is None:
                raise ValueError("truncated Change")
        elif wt == 2:
            ln, p = _read_uvarint(payload, p)
            if ln is None or p + ln > len(payload):
                raise ValueError("truncated Change")
            v, p = payload[p:p + ln], p + ln
            if num in (1, 2):
                v = v.decode()
        else:
            raise ValueError(f"wire type {wt} in a Change")
        fields[names.get(num, num)] = v
    return type_id, fields, at + n


def reply_fault(frame_type: int, fields, kind: str, seq: int,
                dig: bytes) -> str | None:
    """None where a parsed reply frame is the right answer for item
    `seq`; else what is wrong with it."""
    if frame_type != TYPE_CHANGE:
        return f"reply frame of type {frame_type}"
    want = {"key": f"{kind}-{seq}", "change": seq, "from": 0, "to": 1,
            "value": dig, "subset": f"digest:{kind}"}
    for k, v in want.items():
        if fields.get(k) != v:
            if k == "value":
                return f"{kind}-{seq}: digest differs from hashlib"
            return f"{kind}-{seq}: {k} is {fields.get(k)!r}, not {v!r}"
    return None
