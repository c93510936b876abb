"""Multibuffer framing — the L1 wire codec.

Every frame on the wire is (reference: README.md:63-71)::

    | varint( len(payload) + 1 ) | 1-byte type id | payload |

The framed length counts the id byte, which is why the decoder subtracts one
when computing how many payload bytes follow (reference: decode.js:255).

Type ids (reference: encode.js:112 / decode.js:151,155; 0 is reserved for
"scanning a header"):
"""

from __future__ import annotations

from .varint import MAX_VARINT_LEN, decode_uvarint, encode_uvarint

TYPE_HEADER = 0  # parser state only; never a valid frame id
TYPE_CHANGE = 1
TYPE_BLOB = 2
# Columnar bulk-change frame (this package's negotiated extension; NOT
# part of the reference wire — see WIRE.md "ChangeBatch" and PARITY.md).
# Emitted only to peers that advertised CAP_CHANGE_BATCH; a reference
# decoder receiving one fails with its standard unknown-type error,
# which is exactly why the capability handshake exists.
TYPE_CHANGE_BATCH = 3
# Rateless reconciliation frame (negotiated extension, WIRE.md
# "Reconcile"): coded-symbol runs and the begin/more/done/fail control
# messages of the anti-entropy protocol (wire/reconcile_codec.py).
# Same old-peer story as ChangeBatch: never emitted without
# CAP_RECONCILE, unknown-type error otherwise.
TYPE_RECONCILE = 4
# Content-addressed snapshot frame (negotiated extension, WIRE.md
# "Snapshot"): the bootstrap protocol for joiners trimmed past the
# broadcast retention window — manifest, weighted coded-symbol chunk
# reconciliation, and verified chunk transfer
# (wire/snapshot_codec.py).  Same old-peer story as ChangeBatch /
# Reconcile: never emitted without CAP_SNAPSHOT, unknown-type error
# otherwise.
TYPE_SNAPSHOT = 5

KNOWN_TYPES = (TYPE_CHANGE, TYPE_BLOB, TYPE_CHANGE_BATCH, TYPE_RECONCILE,
               TYPE_SNAPSHOT)

# -- capability negotiation (WIRE.md "Capability negotiation") --------------
#
# Capability masks are exchanged OUT OF BAND (session setup / app
# handshake): a session's wire is unidirectional, so the receiving peer
# advertises what it can parse and the encoder is constructed with (or
# later told via Encoder.negotiate) the intersection.  An encoder that
# was never told anything assumes 0 — the reference wire, byte-exact.
CAP_CHANGE_BATCH = 1  # peer parses TYPE_CHANGE_BATCH frames
CAP_RECONCILE = 2  # peer parses TYPE_RECONCILE frames
CAP_SNAPSHOT = 4  # peer parses TYPE_SNAPSHOT frames

# Everything this package's Decoder can parse (the mask a receiver
# advertises during session setup).
LOCAL_CAPS = CAP_CHANGE_BATCH | CAP_RECONCILE | CAP_SNAPSHOT

# Upper bound on header size: 10 varint bytes + 1 id byte.
MAX_HEADER_LEN = MAX_VARINT_LEN + 1


def frame_header(payload_len: int, type_id: int) -> bytes:
    """Build the wire header for a frame with ``payload_len`` payload bytes.

    The reference amortizes header allocation through a shared 65536-byte pool
    (reference: encode.js:6-7,124-137); in Python small-bytes construction is
    already pooled by the allocator, so the header is built directly.
    Single-byte-varint frames (payload < 127 bytes — every digest reply
    and most change records) skip the generic varint encoder.
    """
    if payload_len < 127:
        return bytes((payload_len + 1, type_id))
    return encode_uvarint(payload_len + 1) + bytes((type_id,))


def frame(type_id: int, payload: bytes) -> bytes:
    """A complete frame: header + payload. Used by tests and golden fixtures."""
    return frame_header(len(payload), type_id) + payload


def header_len(payload_len: int) -> int:
    """Byte length of ``frame_header(payload_len, ·)``: the varint of
    ``payload_len + 1`` plus the id byte.  The tracing layer uses this
    to recover a frame's wire START offset (and total wire length) from
    its payload length alone — both peers must compute the same number,
    so it lives here next to the encoder it mirrors."""
    if payload_len < 127:
        return 2
    v = payload_len + 1
    n = 1
    while v >= 0x80:
        v >>= 7
        n += 1
    return n + 1


def frame_wire_len(payload_len: int) -> int:
    """Total wire bytes of a frame with ``payload_len`` payload bytes."""
    return header_len(payload_len) + payload_len


def iter_frames(wire):
    """Walk a complete recorded frame stream: yields ``(start, type_id,
    payload_start, end)`` per frame, where ``wire[payload_start:end]``
    is the payload and ``wire[start:end]`` the whole frame.  The ONE
    owner of the header walk over recorded wire (cold-log replay) —
    every hand-rolled copy of the
    varint/id-byte slicing is a layout fork that must track header
    changes in lockstep."""
    at = 0
    total = len(wire)
    while at < total:
        flen, used = decode_uvarint(wire[at:at + MAX_VARINT_LEN])
        end = at + used + flen
        yield at, wire[at + used], at + used + 1, end
        at = end


class ProtocolError(Exception):
    """Raised (and passed to destroy) on malformed wire data.

    The reference's sole detected fault is an unknown type id
    (reference: decode.js:159-161); this codec also rejects oversized varint
    headers.

    Structured context (ROBUSTNESS.md): a failure that can name where in
    the session it happened carries ``frame`` (0-based index of the frame
    being parsed/delivered when the fault surfaced), ``offset`` (wire
    bytes accepted up to the fault), and ``cause`` (the underlying
    exception, e.g. the ``OSError`` of a dead transport).  All three are
    optional so the bare ``ProtocolError("msg")`` form keeps working;
    when present they are folded into ``str(err)`` so even unstructured
    logging shows them.
    """

    def __init__(self, message: str = "", *, frame: int | None = None,
                 offset: int | None = None,
                 cause: BaseException | None = None):
        self.frame = frame
        self.offset = offset
        self.cause = cause
        context = []
        if frame is not None:
            context.append(f"frame={frame}")
        if offset is not None:
            context.append(f"byte={offset}")
        if cause is not None:
            context.append(f"cause={type(cause).__name__}: {cause}")
        super().__init__(
            f"{message} [{', '.join(context)}]" if context else message
        )
