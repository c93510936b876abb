"""Encoder — the producing end of a replication session.

Capability parity with the reference Encoder (reference: encode.js:46-151),
re-designed as a pull-based Python object instead of a Node Readable:

* ``change(change, on_flush)`` frames a protobuf Change (type id 1).
* ``blob(length, on_flush)`` opens a streamed blob (type id 2); returns a
  :class:`BlobWriter`. The frame length must be declared up front because the
  wire header precedes the data (reference: encode.js:79).
* **Blob FIFO discipline**: any number of blobs may be *open* concurrently but
  their bytes hit the wire strictly in creation order — the second and later
  blobs are corked at creation and uncorked when the head finishes
  (reference: encode.js:87-96). Writes to a corked blob are parked.
* **Change parking**: a change submitted while any blob is open is parked and
  replayed once the blob queue drains, so changes are ordered after all blobs
  that were open at submit time (reference: encode.js:104-107, replay at :95).
* **Backpressure**: the consumer pulls with :meth:`read`; ``on_flush``
  callbacks fire when the corresponding bytes have actually been pulled —
  the pull is this design's analogue of the Readable drain that times flush
  callbacks in the reference (reference: encode.js:139-151).
* ``finalize()`` marks EOF; :meth:`read` returns ``None`` once drained
  (reference: encode.js:119-122 pushes EOF on the Readable).
* Counters ``bytes`` / ``changes`` / ``blobs`` mirror the reference's passive
  counters (reference: encode.js:51-53).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from time import monotonic as _now
from typing import Callable, Optional

from ..obs.metrics import OBS as _OBS, counter as _counter, \
    histogram as _histogram
from ..obs.tracing import trace_instant as _trace_instant
from ..obs import wirecost as _wirecost
from ..wire.change_codec import Change, _check_uint32, \
    _encode_change_with, _fastpath_mod, encode_change
from ..wire.framing import CAP_CHANGE_BATCH, CAP_RECONCILE, CAP_SNAPSHOT, \
    TYPE_BLOB, TYPE_CHANGE, TYPE_CHANGE_BATCH, TYPE_RECONCILE, \
    TYPE_SNAPSHOT, frame_header, frame_wire_len, header_len as _header_len

OnDone = Optional[Callable[[], None]]

# Telemetry handles, hoisted at import so the disabled path at every
# instrumentation site is one `_OBS.on` attribute load (OBSERVABILITY.md).
_M_ENC_BYTES = _counter("encoder.bytes")
_M_ENC_CHANGES = _counter("encoder.changes")
_M_ENC_BLOBS = _counter("encoder.blobs")
_M_ENC_BLOB_CHUNKS = _counter("encoder.blob.chunks")
_M_ENC_PARKED = _counter("encoder.parked.bytes")
# backpressure park time: how long bytes sat corked/parked behind the
# blob FIFO before reaching the wire queue
_H_ENC_PARK = _histogram("encoder.park.seconds")
# negotiated ChangeBatch frames (OBSERVABILITY.md "wire.batch.*"):
# frames/rows emitted columnar, and the wire bytes the columnar layout
# saved vs framing the same rows per-record (exact arithmetic, not an
# estimate — see batch_codec.estimate_per_record_bytes)
_M_BATCH_FRAMES = _counter("wire.batch.frames")
_M_BATCH_ROWS = _counter("wire.batch.rows")
_M_BATCH_SAVED = _counter("wire.batch.bytes_saved")
# negotiated reconcile frames (OBSERVABILITY.md "reconcile.*"): control
# + symbol-run frames emitted, and their total wire volume — the
# anti-entropy protocol's entire communication cost rides these
_M_RC_FRAMES = _counter("reconcile.frames")
_M_RC_WIRE = _counter("reconcile.wire_bytes")
# snapshot protocol frames emitted (OBSERVABILITY.md "snapshot.*")
_M_SN_FRAMES = _counter("snapshot.frames")
_M_SN_WIRE = _counter("snapshot.wire_bytes")

DEFAULT_HIGH_WATER = 64 * 1024


@dataclasses.dataclass
class BatchPolicy:
    """Flush policy for negotiated columnar ``ChangeBatch`` framing.

    Rows accumulate until any bound trips: ``max_rows`` / ``max_bytes``
    (approximate payload volume), ``max_delay`` seconds since the first
    pending row (checked on the next submit — there is no timer thread;
    latency-sensitive producers call :meth:`Encoder.flush_batch`), or an
    *uncork*: a consumer pulling :meth:`Encoder.read` while the queue is
    otherwise dry flushes what is pending, so a drained transport never
    waits on a half-full batch.  A blob open or ``finalize()`` always
    flushes first (frame order is submission order).
    """

    max_rows: int = 4096
    max_bytes: int = 1 << 20
    max_delay: float | None = None


class EncoderDestroyedError(Exception):
    pass


class BlobLengthError(Exception):
    """Writes did not match the declared blob length."""


class BlobWriter:
    """Write side of one streamed blob.

    Mirrors the encoder-side BlobStream (reference: encode.js:11-44): chunks
    forward into the parent's output queue; while corked (not head of the blob
    FIFO) writes are parked and flushed on uncork. Unlike the reference —
    which never validates payload size against the declared frame length —
    this writer raises :class:`BlobLengthError` on overflow or short ``end()``,
    because a mismatch silently desyncs the wire.
    """

    def __init__(self, encoder: "Encoder", length: int, on_flush: OnDone = None):
        self._encoder = encoder
        self.length = length
        self._on_flush = on_flush
        self._written = 0
        self._corked = False
        self._parked: list[tuple[bytes, OnDone, float | None]] = []
        self._ended = False
        self._finished = False
        self._tag_on_uncork = False  # corked blob: frame span deferred
        self.destroyed = False

    # -- public API ---------------------------------------------------------

    def write(self, data, on_flush: OnDone = None) -> bool:
        """Append blob payload bytes. Returns False when the encoder's output
        buffer is above the high-water mark (the caller should wait for
        :meth:`Encoder.on_drain`)."""
        if self.destroyed or self._encoder.destroyed:
            raise EncoderDestroyedError("write after destroy")
        if self._ended:
            raise BlobLengthError("write after end()")
        if isinstance(data, str):
            data = data.encode("utf-8")
        elif not isinstance(data, (bytes, bytearray, memoryview)):
            data = bytes(data)
        if self._written + len(data) > self.length:
            err = BlobLengthError(
                f"blob overflow: declared {self.length}, writing past it "
                f"({self._written} + {len(data)})"
            )
            self._encoder.destroy(err)
            raise err
        self._written += len(data)
        if _OBS.on:
            _M_ENC_BLOB_CHUNKS.inc()
        if self._corked:
            self._park(bytes(data), on_flush)
            return not self._encoder._above_high_water()
        return self._encoder._push(data, on_flush)

    def end(self, data=None, on_flush: OnDone = None) -> None:
        """Finish the blob (optionally writing a final chunk)."""
        if data is not None:
            self.write(data, on_flush)
        elif on_flush is not None:
            # fire once the blob's bytes are flushed
            prev = self._on_flush
            if prev is None:
                self._on_flush = on_flush
            else:
                def both(a=prev, b=on_flush):
                    a()
                    b()
                self._on_flush = both
        if self._ended:
            return
        self._ended = True
        if self._written != self.length:
            err = BlobLengthError(
                f"blob ended short: declared {self.length}, wrote {self._written}"
            )
            self._encoder.destroy(err)
            raise err
        if not self._corked:
            self._finish()

    def destroy(self, err: Exception | None = None) -> None:
        """Tear down this blob and the whole session — destroying either side
        of a blob destroys its parent (reference: encode.js:22-28)."""
        if self.destroyed:
            return
        self.destroyed = True
        self._encoder.destroy(err)

    # -- internal -----------------------------------------------------------

    def _cork(self) -> None:
        self._corked = True

    def _park(self, data: bytes, cb: OnDone) -> None:
        """Parked bytes count toward the encoder's high-water mark so
        backpressure stays honest while the head blob streams."""
        # third slot: park timestamp (None while telemetry is off) —
        # _uncork turns it into the encoder.park.seconds histogram
        self._parked.append((data, cb, _now() if _OBS.on else None))
        self._encoder._parked_bytes += len(data)
        if _OBS.on:
            _M_ENC_PARKED.inc(len(data))

    def _uncork(self) -> None:
        """Flush parked chunks into the parent; if already ended, finish —
        cascading to the next queued blob (reference: encode.js:30-35,92-96)."""
        if not self._corked:
            return
        self._corked = False
        if self._tag_on_uncork:
            self._tag_on_uncork = False
            if _OBS.on:
                # the first parked chunk is this blob's header: the
                # encoder's byte count right now IS the frame's wire
                # start offset
                if _OBS.frames:
                    _trace_instant("encoder.frame", offset=self._encoder.bytes,
                                   kind="blob",
                                   wire_len=frame_wire_len(self.length))
                self._encoder._lit_cost_blob(self.length)
        for data, cb, t0 in self._parked:
            self._encoder._parked_bytes -= len(data)
            if t0 is not None and _OBS.on:
                _H_ENC_PARK.observe(_now() - t0)
            self._encoder._push(data, cb)
        self._parked.clear()
        if self._ended:
            self._finish()

    def _finish(self) -> None:
        if self._finished:
            return
        self._finished = True
        if self._on_flush is not None:
            # Deliver the blob-level flush when the last pushed byte drains.
            self._encoder._after_flush(self._on_flush)
        self._encoder._blob_finished(self)


class Encoder:
    """Pull-based frame producer. See module docstring for semantics."""

    # the wire cost plane's link label (ISSUE 20): owners carrying more
    # than one session overwrite it per instance (the sidecar names it
    # after the session key) — a collector label, runtime by design
    cost_link = "session"

    def __init__(self, high_water: int = DEFAULT_HIGH_WATER,
                 peer_caps: int = 0,
                 batch_policy: BatchPolicy | None = None):
        self.bytes = 0
        self.changes = 0
        self.blobs = 0
        # capability mask the RECEIVING peer advertised (WIRE.md
        # "Capability negotiation"); 0 = assume a reference peer, emit
        # the reference wire byte-exactly.  CAP_CHANGE_BATCH switches
        # change() to columnar accumulation behind `batch_policy`.
        self.peer_caps = peer_caps
        self._batch_policy = batch_policy if batch_policy is not None \
            else BatchPolicy()
        # pending ChangeBatch rows: prepared (validated, utf-8 encoded)
        # tuples + their flush callbacks; byte volume rides the
        # high-water accounting like parked changes do
        self._batch_rows: list[tuple] = []
        self._batch_cbs: list[Callable[[], None]] = []
        self._batch_pending_bytes = 0
        self._batch_t0: float | None = None
        self.destroyed = False
        self.finalized = False
        self.finished = False  # terminal: drained past finalize, or destroyed
        self._high_water = high_water
        # queue of (payload: bytes, on_consumed: OnDone); payloads are wire
        # bytes (headers and data alike).
        self._queue: deque[tuple[bytes, OnDone]] = deque()
        self._queued_bytes = 0
        self._parked_bytes = 0  # bytes held in corked blobs / parked changes
        self._open_blobs: deque[BlobWriter] = deque()
        # Parked changes are encoded at submit time (catching bad input early
        # and making the parked bytes countable); framed on replay.
        self._parked_changes: list[tuple[bytes, OnDone, float | None]] = []
        self._drain_cbs: list[Callable[[], None]] = []
        self._error_cbs: list[Callable[[Exception | None], None]] = []
        self._finish_cbs: list[Callable[[], None]] = []
        self._finalize_cb: OnDone = None
        # Consumer hook (set by session.pipe.Pipe): called whenever new wire
        # bytes become readable, so a connected pump keeps flowing on late
        # writes — the pull-based stand-in for Node's 'readable' event.
        self._on_readable: Optional[Callable[[], None]] = None
        # Resume tee (see session.resume.WireJournal): every byte read()
        # hands out is also appended here, so a reconnect can replay the
        # bytes a dead transport lost.
        self._journal = None

    def _attach_readable(self, cb: Callable[[], None]) -> None:
        """Claim the single readable-hook slot.  A second pump silently
        overwriting the first would starve it forever — fail loudly."""
        if self._on_readable is not None:
            raise RuntimeError(
                "encoder is already attached to a pump/pipe; detach it first"
            )
        self._on_readable = cb

    def _detach_readable(self) -> None:
        self._on_readable = None

    def attach_journal(self, journal) -> None:
        """Tee every wire byte :meth:`read` returns into ``journal``
        (anything with ``append(bytes)`` — canonically a
        :class:`~.resume.WireJournal`), so the session can resume from a
        receiver checkpoint after a transport failure.  The journal sees
        bytes in exact wire order because ``read`` is the single exit
        point of the output queue.

        Journal positions are ABSOLUTE wire offsets: attaching after
        bytes were already read out aligns the journal's window past
        them (via ``journal.seek``) — silently recording them at offset
        0 would make every ``read_from(checkpoint.wire_offset)`` replay
        the wrong bytes."""
        delivered = self.bytes - self._queued_bytes  # already read out
        if delivered:
            seek = getattr(journal, "seek", None)
            if seek is None:
                raise RuntimeError(
                    f"encoder already emitted {delivered} byte(s) and the "
                    "journal cannot seek; attach before the first read")
            seek(delivered)
        self._journal = journal

    # -- capability negotiation ---------------------------------------------

    def negotiate(self, peer_caps: int) -> None:
        """Adopt the receiving peer's advertised capability mask (learned
        out of band — session setup, app handshake; WIRE.md).  Takes
        effect for subsequent submissions; revoking ``CAP_CHANGE_BATCH``
        re-frames any pending rows as per-record ``Change`` frames —
        the revocation means the peer cannot parse a batch frame, so
        one must never be emitted after it."""
        had_batch = self._batching
        self.peer_caps = peer_caps
        if had_batch and not self._batching:
            self._flush_pending_per_record()

    @property
    def _batching(self) -> bool:
        return bool(self.peer_caps & CAP_CHANGE_BATCH) \
            and not self.destroyed

    # -- public API ---------------------------------------------------------

    def change(self, change: Change | dict, on_flush: OnDone = None) -> bool:
        """Frame a Change. If any blob is open the change is parked and
        replayed when the blob queue drains (reference: encode.js:102-117).

        With ``CAP_CHANGE_BATCH`` negotiated and no blob open, the change
        instead joins the pending columnar batch (validated now, framed
        at flush — see :class:`BatchPolicy` for when that happens)."""
        if self.destroyed:
            raise EncoderDestroyedError("change after destroy")
        if self.finalized:
            raise EncoderDestroyedError("change after finalize")
        if self._batching and not self._open_blobs:
            self._batch_append(self._prepare_row(change), on_flush)
            return not self._above_high_water()
        payload = encode_change(change)
        if self._open_blobs:
            self._parked_changes.append(
                (payload, on_flush, _now() if _OBS.on else None))
            self._parked_bytes += len(payload)
            if _OBS.on:
                _M_ENC_PARKED.inc(len(payload))
            return not self._above_high_water()
        return self._frame_change(payload, on_flush)

    def change_many(self, records, on_flush: OnDone = None) -> bool:
        """Submit a whole run of changes with per-batch (not per-row)
        overhead: the fastpath gate is bound ONCE, the framed bytes land
        in ONE queue entry (one readable wakeup, one journal tee), and
        ``on_flush`` fires when the run's bytes drain.  Wire bytes are
        identical to calling :meth:`change` per record — this is the
        bulk shape of the same API, for log-construction-scale callers.
        """
        if self.destroyed:
            raise EncoderDestroyedError("change after destroy")
        if self.finalized:
            raise EncoderDestroyedError("change after finalize")
        if not isinstance(records, (list, tuple)):
            records = list(records)
        if self._open_blobs:
            # ordering behind the blob FIFO is per-record machinery;
            # park each (rare shape — bulk producers don't interleave)
            ok = True
            for i, rec in enumerate(records):
                ok = self.change(
                    rec, on_flush if i == len(records) - 1 else None)
            return ok
        if self._batching:
            prepared = [self._prepare_row(r) for r in records]
            for i, row in enumerate(prepared):
                self._batch_append(
                    row, on_flush if i == len(prepared) - 1 else None,
                    defer_flush=True)
            self._maybe_flush_batch()
            return not self._above_high_water()
        fp = _fastpath_mod()  # bound once for the whole run
        out = bytearray()
        n = 0
        plen = 0
        obs_on = _OBS.on
        for rec in records:
            payload = _encode_change_with(fp, rec)
            header = frame_header(len(payload), TYPE_CHANGE)
            if obs_on:
                if _OBS.frames:
                    _trace_instant("encoder.frame",
                                   offset=self.bytes + len(out),
                                   kind="change",
                                   wire_len=len(header) + len(payload))
                plen += len(payload)
            out += header
            out += payload
            n += 1
        if not n:
            if on_flush is not None:
                self._after_flush(on_flush)
            return not self._above_high_water()
        self.changes += n
        if obs_on:
            _M_ENC_CHANGES.inc(n)
            # run totals: framing = framed bytes minus payload bytes
            self._lit_cost_change(len(out) - plen, plen, n)
        return self._push(bytes(out), on_flush)

    # -- ChangeBatch accumulation -------------------------------------------

    @staticmethod
    def _prepare_row(change: Change | dict) -> tuple:
        """Validate + normalize one record at SUBMIT time (same doctrine
        as parked changes encoding eagerly: bad input surfaces at the
        call that supplied it, not at some later flush).  Field
        extraction and error classes mirror ``_encode_change_with``."""
        if isinstance(change, dict):
            if "from" in change:
                fr = change["from"]
            elif "from_" in change:
                fr = change["from_"]
            else:
                raise KeyError("from")  # required, same as from_dict
            key = change["key"]
            cg = change["change"]
            to = change["to"]
            value = change.get("value")
            subset = change.get("subset")
        else:
            key = change.key
            cg = change.change
            fr = change.from_
            to = change.to
            value = change.value
            subset = change.subset
        if key is None:
            raise ValueError("Change.key is required")
        return (
            key.encode("utf-8"),
            _check_uint32("change", cg),
            _check_uint32("from", fr),
            _check_uint32("to", to),
            None if value is None else bytes(value),
            None if subset is None else subset.encode("utf-8"),
        )

    def _note_batch_rows(self, rows: list[tuple]) -> None:
        """Hook: one call per batch flush with the prepared row tuples,
        before the frame reaches the queue (the digest encoder submits
        each row's canonical per-record encoding here).  Base: no-op."""

    def _flush_pending_per_record(self) -> None:
        """Capability revocation path: the peer can no longer parse
        batch frames, so pending rows re-frame as per-record ``Change``
        frames (their flush callbacks fire when the run drains, same
        timing a batch flush would have given them)."""
        rows, self._batch_rows = self._batch_rows, []
        if not rows:
            return
        cbs, self._batch_cbs = self._batch_cbs, []
        self._batch_pending_bytes = 0
        self._batch_t0 = None
        fp = _fastpath_mod()  # bound once for the run

        def all_cbs():
            for cb in cbs:
                cb()

        last = len(rows) - 1
        for i, (key, cg, fr, to, val, sub) in enumerate(rows):
            payload = _encode_change_with(fp, {
                "key": key.decode("utf-8"), "change": cg, "from": fr,
                "to": to, "value": val,
                "subset": None if sub is None else sub.decode("utf-8"),
            })
            self._frame_change(
                payload, all_cbs if (i == last and cbs) else None)

    def _batch_append(self, row: tuple, on_flush: OnDone,
                      defer_flush: bool = False) -> None:
        if not self._batch_rows:
            self._batch_t0 = _now()
        self._batch_rows.append(row)
        if on_flush is not None:
            self._batch_cbs.append(on_flush)
        # approximate pending volume: heap bytes + fixed columns
        self._batch_pending_bytes += (
            len(row[0]) + (len(row[4]) if row[4] is not None else 0)
            + (len(row[5]) if row[5] is not None else 0) + 24)
        if not defer_flush:
            self._maybe_flush_batch()

    def _maybe_flush_batch(self) -> None:
        pol = self._batch_policy
        if (len(self._batch_rows) >= pol.max_rows
                or self._batch_pending_bytes >= pol.max_bytes
                or (pol.max_delay is not None and self._batch_t0 is not None
                    and _now() - self._batch_t0 >= pol.max_delay)):
            self.flush_batch()

    # -- wire cost lit helpers (ISSUE 20) ------------------------------------
    # Each hot path forks ONCE on `_OBS.on`; the helper below the fork
    # holds every wirecost symbol, so the dark twin's bytecode provably
    # references none of them (tests/test_wirecost.py asserts it) and
    # the disabled cost stays one attribute load.  The frame CLASS is a
    # string literal at every call (the datlint obs-discipline
    # contract: the class vocabulary must stay greppable).

    def _lit_cost_change(self, framing: int, payload: int,
                         frames: int = 1) -> None:
        _wirecost.account("change", self.cost_link, "tx", payload,
                          framing, frames)

    def _lit_cost_batch(self, framing: int, payload: int,
                        saved: int) -> None:
        _wirecost.account("change_batch", self.cost_link, "tx", payload,
                          framing)
        if saved > 0:
            _wirecost.note_saved(self.cost_link, "tx", saved)

    def _lit_cost_reconcile(self, framing: int, payload: int) -> None:
        _wirecost.account("reconcile", self.cost_link, "tx", payload,
                          framing)

    def _lit_cost_snapshot(self, framing: int, payload: int) -> None:
        _wirecost.account("snapshot", self.cost_link, "tx", payload,
                          framing)

    def _lit_cost_blob(self, length: int) -> None:
        # accrued in full at header time — the same moment the
        # encoder.frame tag prices the whole frame (wire_len includes
        # the declared payload the chunks will stream)
        _wirecost.account("blob", self.cost_link, "tx", length,
                          _header_len(length))

    def flush_batch(self) -> None:
        """Frame every pending batch row NOW as one ``TYPE_CHANGE_BATCH``
        frame (no-op when nothing is pending)."""
        rows, self._batch_rows = self._batch_rows, []
        if not rows:
            return
        cbs, self._batch_cbs = self._batch_cbs, []
        self._batch_pending_bytes = 0
        self._batch_t0 = None
        # flush-side tap BEFORE the frame is queued — the batch twin of
        # _frame_change's submit-before-frame ordering (the TPU encoder
        # submits per-row digests of the canonical encodings here)
        self._note_batch_rows(rows)
        from ..wire import batch_codec

        payload = batch_codec.encode_rows(rows)
        header = frame_header(len(payload), TYPE_CHANGE_BATCH)
        n = len(rows)
        self.changes += n
        if _OBS.on:
            _M_ENC_CHANGES.inc(n)
            _M_BATCH_FRAMES.inc()
            _M_BATCH_ROWS.inc(n)
            import numpy as np

            est = batch_codec.estimate_per_record_bytes(
                np.asarray([len(r[0]) for r in rows], np.int64),
                np.asarray([-1 if r[5] is None else len(r[5])
                            for r in rows], np.int64),
                np.asarray([-1 if r[4] is None else len(r[4])
                            for r in rows], np.int64),
                np.asarray([r[1] for r in rows], np.uint32),
                np.asarray([r[2] for r in rows], np.uint32),
                np.asarray([r[3] for r in rows], np.uint32),
            )
            saved = est - (len(header) + len(payload))
            if saved > 0:
                _M_BATCH_SAVED.inc(saved)
            if _OBS.frames:
                _trace_instant("encoder.frame", offset=self.bytes,
                               kind="change_batch", rows=n,
                               wire_len=len(header) + len(payload))
            self._lit_cost_batch(len(header), len(payload), int(saved))
        if len(cbs) > 1:
            def all_cbs(cbs=cbs):
                for cb in cbs:
                    cb()
            cb = all_cbs
        else:
            cb = cbs[0] if cbs else None
        self._push(header + payload, cb)

    def _frame_change(self, payload: bytes, on_flush: OnDone) -> bool:
        self.changes += 1
        header = frame_header(len(payload), TYPE_CHANGE)
        if _OBS.on:
            _M_ENC_CHANGES.inc()
            # causal key: self.bytes BEFORE the header push is the wire
            # offset this frame starts at — the same number the peer's
            # decoder computes for the same frame (obs/tracing.py)
            if _OBS.frames:
                _trace_instant("encoder.frame", offset=self.bytes,
                               kind="change",
                               wire_len=len(header) + len(payload))
            self._lit_cost_change(len(header), len(payload))
        self._push(header, None)
        return self._push(payload, on_flush)

    def reconcile_frame(self, payload, on_flush: OnDone = None) -> bool:
        """Frame one reconcile protocol message (``TYPE_RECONCILE``;
        payload built by :mod:`..wire.reconcile_codec`).

        Strictly negotiated: raises unless the receiving peer advertised
        ``CAP_RECONCILE`` — an un-negotiated encoder therefore emits the
        reference wire byte-exactly (same golden contract as
        ChangeBatch).  Pending batch rows flush first (frame order is
        submission order); an open blob is an API error — a control
        frame cannot be parked behind a streaming payload without
        reordering the wire, and the reconcile driver never interleaves
        the two."""
        if self.destroyed:
            raise EncoderDestroyedError("reconcile_frame after destroy")
        if self.finalized:
            raise EncoderDestroyedError("reconcile_frame after finalize")
        if not (self.peer_caps & CAP_RECONCILE):
            raise ValueError(
                "peer did not advertise CAP_RECONCILE; reconcile frames "
                "cannot be emitted to it (WIRE.md capability negotiation)"
            )
        if self._open_blobs:
            raise ValueError(
                "reconcile_frame with a blob open is unsupported"
            )
        if self._batch_rows:
            self.flush_batch()
        payload = bytes(payload)
        header = frame_header(len(payload), TYPE_RECONCILE)
        if _OBS.on:
            _M_RC_FRAMES.inc()
            _M_RC_WIRE.inc(len(header) + len(payload))
            if _OBS.frames:
                _trace_instant("encoder.frame", offset=self.bytes,
                               kind="reconcile",
                               wire_len=len(header) + len(payload))
            self._lit_cost_reconcile(len(header), len(payload))
        return self._push(header + payload, on_flush)

    def snapshot_frame(self, payload, on_flush: OnDone = None) -> bool:
        """Frame one snapshot protocol message (``TYPE_SNAPSHOT``;
        payload built by :mod:`..wire.snapshot_codec`).

        Strictly negotiated: raises unless the receiving peer advertised
        ``CAP_SNAPSHOT`` — an un-negotiated encoder therefore emits the
        reference wire byte-exactly (same golden contract as ChangeBatch
        and Reconcile).  Pending batch rows flush first (frame order is
        submission order); an open blob is an API error — the snapshot
        driver never interleaves the two."""
        if self.destroyed:
            raise EncoderDestroyedError("snapshot_frame after destroy")
        if self.finalized:
            raise EncoderDestroyedError("snapshot_frame after finalize")
        if not (self.peer_caps & CAP_SNAPSHOT):
            raise ValueError(
                "peer did not advertise CAP_SNAPSHOT; snapshot frames "
                "cannot be emitted to it (WIRE.md capability negotiation)"
            )
        if self._open_blobs:
            raise ValueError(
                "snapshot_frame with a blob open is unsupported"
            )
        if self._batch_rows:
            self.flush_batch()
        payload = bytes(payload)
        header = frame_header(len(payload), TYPE_SNAPSHOT)
        if _OBS.on:
            _M_SN_FRAMES.inc()
            _M_SN_WIRE.inc(len(header) + len(payload))
            if _OBS.frames:
                _trace_instant("encoder.frame", offset=self.bytes,
                               kind="snapshot",
                               wire_len=len(header) + len(payload))
            self._lit_cost_snapshot(len(header), len(payload))
        return self._push(header + payload, on_flush)

    def blob(self, length: int, on_flush: OnDone = None) -> BlobWriter:
        """Open a streamed blob of exactly ``length`` bytes. The length is
        required up front — the frame header precedes the data on the wire
        (reference: encode.js:77-100)."""
        if self.destroyed:
            raise EncoderDestroyedError("blob after destroy")
        if self.finalized:
            raise EncoderDestroyedError("blob after finalize")
        if not isinstance(length, int) or length <= 0:
            raise ValueError("blob length is required and must be > 0")
        # frame order is submission order: rows accumulated before this
        # blob must hit the wire before its header
        if self._batch_rows:
            self.flush_batch()
        ws = BlobWriter(self, length, on_flush)
        self.blobs += 1
        if _OBS.on:
            _M_ENC_BLOBS.inc()
        header = frame_header(length, TYPE_BLOB)
        if self._open_blobs:
            ws._cork()
            # the parked header reaches the wire at uncork time — the
            # frame's true wire offset is only known there (_uncork
            # tags it via this flag)
            ws._tag_on_uncork = True
            ws._park(header, None)
        else:
            if _OBS.on:
                if _OBS.frames:
                    _trace_instant("encoder.frame", offset=self.bytes,
                                   kind="blob",
                                   wire_len=len(header) + length)
                self._lit_cost_blob(length)
            self._push(header, None)
        self._open_blobs.append(ws)
        return ws

    def finalize(self, on_flush: OnDone = None) -> None:
        """Graceful end of session: after the queue drains, :meth:`read`
        reports EOF (reference: encode.js:119-122)."""
        if self.destroyed:
            raise EncoderDestroyedError("finalize after destroy")
        if self._open_blobs:
            raise EncoderDestroyedError(
                f"finalize with {len(self._open_blobs)} blob(s) still open"
            )
        if self._batch_rows:
            self.flush_batch()
        self.finalized = True
        self._finalize_cb = on_flush
        if not self._queue:
            if on_flush is not None:
                cb, self._finalize_cb = self._finalize_cb, None
                cb()
            self._fire_finish()
        if self._on_readable is not None:
            self._on_readable()  # let a connected pump observe EOF

    def read(self, max_bytes: int = -1) -> bytes | None:
        """Pull up to ``max_bytes`` of wire data (all buffered if -1).

        Returns ``b''`` when nothing is buffered yet, or ``None`` for EOF
        (finalized and fully drained). Firing of ``on_flush`` callbacks is
        tied to their bytes leaving this buffer — the pull-based analogue of
        the reference's `_read`-driven drain (reference: encode.js:147-151).
        """
        if self.destroyed:
            raise EncoderDestroyedError("read after destroy")
        if not self._queue and self._batch_rows:
            # uncork: a consumer pulling a dry queue gets what is
            # pending instead of waiting out the batch policy
            self.flush_batch()
        if not self._queue:
            if self.finalized:
                return None
            return b""
        out = bytearray()
        fired: list[Callable[[], None]] = []
        while self._queue and (max_bytes < 0 or len(out) < max_bytes):
            payload, cb = self._queue[0]
            room = len(payload) if max_bytes < 0 else max_bytes - len(out)
            if len(payload) <= room:
                out += payload
                self._queue.popleft()
                self._queued_bytes -= len(payload)
                if cb is not None:
                    fired.append(cb)
            else:
                out += payload[:room]
                self._queue[0] = (payload[room:], cb)
                self._queued_bytes -= room
                break
        data = bytes(out)
        if _OBS.on and data:
            _M_ENC_BYTES.inc(len(data))
        if self._journal is not None and data:
            # journal BEFORE the flush callbacks: when an on_flush hook
            # acks the journal window, the bytes it acks must be there
            self._journal.append(data)
        below = not self._above_high_water()
        for cb in fired:
            cb()
        if below and self._drain_cbs:
            cbs, self._drain_cbs = self._drain_cbs, []
            for cb in cbs:
                cb()
        if self.finalized and not self._queue:
            if self._finalize_cb is not None:
                cb, self._finalize_cb = self._finalize_cb, None
                cb()
            self._fire_finish()
        return data

    @property
    def buffered_bytes(self) -> int:
        return self._queued_bytes

    def writable(self) -> bool:
        return not self._above_high_water()

    def on_drain(self, cb: Callable[[], None]) -> None:
        """One-shot callback when the buffer falls below the high-water mark."""
        if self._above_high_water():
            self._drain_cbs.append(cb)
        else:
            cb()

    def on_error(self, cb: Callable[[Exception | None], None]) -> None:
        self._error_cbs.append(cb)

    def on_finish(self, cb: Callable[[], None]) -> None:
        """Terminal lifecycle hook, the encoder-side 'close': fires exactly
        once, after the finalized session has fully drained OR after destroy
        (in which case error callbacks fire first — the reference's
        'error' then 'close' ordering, reference: encode.js:73-74)."""
        if self.finished:
            cb()
        else:
            self._finish_cbs.append(cb)

    def _fire_finish(self) -> None:
        if self.finished:
            return
        self.finished = True
        cbs, self._finish_cbs = self._finish_cbs, []
        for cb in cbs:
            cb()

    def destroy(self, err: Exception | None = None) -> None:
        """Fail-fast teardown: destroys every open blob writer
        (reference: encode.js:69-75)."""
        if self.destroyed:
            return
        self.destroyed = True
        for ws in list(self._open_blobs):
            ws.destroyed = True
        self._open_blobs.clear()
        self._queue.clear()
        self._queued_bytes = 0
        self._parked_bytes = 0
        self._parked_changes.clear()
        self._batch_rows.clear()
        self._batch_cbs.clear()
        self._batch_pending_bytes = 0
        for cb in self._error_cbs:
            cb(err)
        # Release parked drain callbacks so a producer gated on the drain
        # signal wakes up and observes the destroyed state (mirrors the
        # decoder releasing its parked write callbacks on destroy).
        cbs, self._drain_cbs = self._drain_cbs, []
        for cb in cbs:
            cb()
        self._fire_finish()

    # -- internal -----------------------------------------------------------

    def _above_high_water(self) -> bool:
        return (self._queued_bytes + self._parked_bytes
                + self._batch_pending_bytes >= self._high_water)

    def _push(self, data, on_consumed: OnDone) -> bool:
        data = bytes(data)
        self.bytes += len(data)
        self._queue.append((data, on_consumed))
        self._queued_bytes += len(data)
        if self._on_readable is not None:
            self._on_readable()
        return not self._above_high_water()

    def _after_flush(self, cb: Callable[[], None]) -> None:
        """Run ``cb`` once everything currently queued has been read."""
        if not self._queue:
            cb()
            return
        payload, prev = self._queue[-1]
        if prev is None:
            self._queue[-1] = (payload, cb)
        else:
            def both(a=prev, b=cb):
                a()
                b()
            self._queue[-1] = (payload, both)

    def _blob_finished(self, ws: BlobWriter) -> None:
        """Head-of-line blob completed: uncork the next and replay parked
        changes (which re-park if blobs remain) — reference: encode.js:92-97."""
        if not self._open_blobs or self._open_blobs[0] is not ws:
            err = AssertionError("blob FIFO assertion failed")
            self.destroy(err)
            raise err
        self._open_blobs.popleft()
        if self._open_blobs:
            self._open_blobs[0]._uncork()
        parked, self._parked_changes = self._parked_changes, []
        for payload, cb, t0 in parked:
            if self._open_blobs:  # a later blob is still open: stay parked
                self._parked_changes.append((payload, cb, t0))
            else:
                self._parked_bytes -= len(payload)
                if t0 is not None and _OBS.on:
                    _H_ENC_PARK.observe(_now() - t0)
                self._frame_change(payload, cb)
