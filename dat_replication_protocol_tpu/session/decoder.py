"""Decoder — the consuming end of a replication session.

Capability parity with the reference Decoder (reference: decode.js:63-262),
re-designed as a push-based incremental parser with an explicit pending
counter instead of Node Writable plumbing:

* :meth:`write` feeds wire bytes; the internal state machine is
  header → (change | blob payload) → header …, slicing without copying on the
  fast path (reference keeps the same discipline, decode.js:217-227,198-201).
* Handlers are registered with :meth:`change` / :meth:`blob` /
  :meth:`finalize` (same registration-style API as the reference,
  decode.js:112-122). Each handler receives a ``done`` callable;
  **backpressure**: while any ``done`` is outstanding, parsing pauses and
  :meth:`write` returns ``False`` — the analogue of the reference withholding
  the Writable's callback (reference: decode.js:87-99,168).
* Unregistered handlers never deadlock the pipeline: changes are dropped,
  blobs drained, finalize auto-acked (reference: decode.js:50-61).
* :meth:`end` invokes the finalize handler after all prior frames are
  consumed, before the session completes — the sentinel-write trick of the
  reference (decode.js:6,124-142) becomes an explicit queued finalization.
* Unknown frame type ids destroy the session with
  :class:`~..wire.framing.ProtocolError` (reference: decode.js:159-161).
* Counters ``bytes`` / ``changes`` / ``blobs`` (reference: decode.js:68-70).
"""

from __future__ import annotations

import threading
from collections import deque
from time import perf_counter as _perf
from typing import Callable, Optional

from .._fastpath_gate import fastpath_mod as _fastpath_mod
from ..obs.events import emit as _emit
from ..obs.flight import FLIGHT as _FLIGHT
from ..obs.metrics import OBS as _OBS, counter as _counter, \
    histogram as _histogram
from ..obs.tracing import trace_instant as _trace_instant
from ..obs.watermarks import WATERMARKS as _WATERMARKS
from ..obs import wirecost as _wirecost
from ..wire.change_codec import Change, decode_change
from ..wire.framing import LOCAL_CAPS, MAX_HEADER_LEN, TYPE_BLOB, \
    TYPE_CHANGE, TYPE_CHANGE_BATCH, TYPE_HEADER, TYPE_RECONCILE, \
    TYPE_SNAPSHOT, ProtocolError
from ..wire.framing import header_len as _header_len
from ..wire.varint import decode_uvarint

OnDone = Optional[Callable[[], None]]

# Telemetry handles, hoisted at import: the disabled path at every
# instrumentation site below is a single `_OBS.on` attribute load — no
# registry lookup, no allocation (OBSERVABILITY.md's budget).
_M_DEC_BYTES = _counter("decoder.bytes")
_M_DEC_CHANGES = _counter("decoder.changes")
_M_DEC_BLOBS = _counter("decoder.blobs")
_M_DEC_BLOB_BYTES = _counter("decoder.blob.bytes")
# blob payload bytes that took a copy on the way to their consumer (a
# registered blob handler's ``bytes``, a digest pipeline that wants one
# ``bytes`` per blob); over decoder.blob.bytes: copies per blob byte
_M_DEC_BLOB_COPIED = _counter("decoder.blob.copied.bytes")
_M_DEC_REQUEUES = _counter("decoder.requeues")
_M_DEC_ERRORS = _counter("decoder.errors")
# columnar ChangeBatch frames dispatched (rows ride decoder.changes)
_M_DEC_BATCH_FRAMES = _counter("decoder.batch.frames")
# receiver-side mirror of wire.batch.bytes_saved (ISSUE 20 satellite):
# the SAME exact arithmetic run against the decoded columns, so sender
# and receiver agree to the byte (tests/test_wirecost.py cross-check)
_M_BATCH_SAVED_RX = _counter("wire.batch.bytes_saved_rx")
# reconcile protocol frames dispatched (OBSERVABILITY.md "reconcile.*")
_M_DEC_RC_FRAMES = _counter("decoder.reconcile.frames")
# snapshot protocol frames dispatched (OBSERVABILITY.md "snapshot.*")
_M_DEC_SN_FRAMES = _counter("decoder.snapshot.frames")
# per-write() dispatch latency: bytes in -> handlers fired (or stalled)
_H_DEC_DISPATCH = _histogram("decoder.dispatch.seconds")

# The bulk-path cursor: frame index and columnar row MUST advance
# together — a frame paired with the wrong row's columns is silent wire
# corruption (round-5 advisor, high).  Machine-checked:
# datlint: coupled-state st["f"], st["row"]


class DecoderDestroyedError(Exception):
    pass


class BlobReader:
    """Read side of one streamed blob, handed to the app's blob handler.

    Chunks are delivered through :meth:`on_data` as they are parsed; chunks
    arriving before a handler is registered are buffered and replayed at
    registration (the Readable-buffer behavior of the reference's BlobStream,
    reference: decode.js:8-48). :meth:`pause` / :meth:`resume` give the app
    per-chunk backpressure: while paused the decoder stops parsing, which
    propagates to the transport.
    """

    def __init__(self, decoder: "Decoder", length: int):
        self._decoder = decoder
        self.length = length
        self.received = 0
        self.ended = False
        self.destroyed = False
        self._data_cb: Optional[Callable[[bytes], None]] = None
        self._end_cbs: list[Callable[[], None]] = []
        self._buffered: list[bytes] = []
        self._paused = False
        # opened by the decoder's default handler: nobody reads a chunk,
        # so the decoder materialises none (Decoder._blob_data)
        self._unread = False

    def on_data(self, cb: Callable[[bytes], None]) -> "BlobReader":
        self._data_cb = cb
        if self._buffered:
            chunks, self._buffered = self._buffered, []
            for c in chunks:
                cb(c)
        return self

    def on_end(self, cb: Callable[[], None]) -> "BlobReader":
        if self.ended:
            cb()
        else:
            self._end_cbs.append(cb)
        return self

    def collect(self, cb: Callable[[bytes], None]) -> "BlobReader":
        """Convenience: buffer the whole blob and deliver it once on end —
        the role `concat-stream` plays in the reference suite
        (reference: test/basic.js:36-40)."""
        parts: list[bytes] = []
        self.on_data(parts.append)
        self.on_end(lambda: cb(b"".join(parts)))
        return self

    def pause(self) -> None:
        """Stop the decoder from parsing further input (chunk granularity)
        until :meth:`resume` — per-chunk backpressure, the analogue of the
        reference's Readable drain accounting (reference: decode.js:35-48)."""
        if self._paused:
            return
        self._paused = True
        self._decoder._paused_readers += 1

    def resume(self) -> None:
        if not self._paused:
            return
        self._paused = False
        self._decoder._paused_readers -= 1
        self._decoder._resume()

    def destroy(self, err: Exception | None = None) -> None:
        """Destroying a blob reader tears down the whole session
        (reference: decode.js:20-26)."""
        if self.destroyed:
            return
        self.destroyed = True
        self._decoder.destroy(err)

    # -- driven by the decoder ---------------------------------------------

    def _deliver(self, chunk: bytes) -> None:
        self.received += len(chunk)
        if self._data_cb is not None:
            self._data_cb(chunk)
        else:
            self._buffered.append(chunk)

    def _finish(self) -> None:
        self.ended = True
        cbs, self._end_cbs = self._end_cbs, []
        for cb in cbs:
            cb()


class _FastAck:
    """One-shot ``done`` for the bulk fast path, cheaper than an ``_up``
    closure: the pending counter is only touched if the handler did NOT
    ack synchronously (the overwhelmingly common case never pays the
    increment/decrement/resume round-trip).

    States: 0 fresh -> 1 acked-before-arming (sync; no pending ever
    taken) / 2 armed (handler kept it async; pending incremented by the
    dispatch loop) -> 3 done (armed ack fired; pending released).  All
    transitions run under the decoder's ``_ack_lock`` so an ack landing
    from another thread between the handler returning and the loop
    arming can neither be lost nor double-counted.
    """

    __slots__ = ("dec", "state")

    def __init__(self, dec: "Decoder") -> None:
        self.dec = dec
        self.state = 0

    def __call__(self) -> None:
        dec = self.dec
        with dec._ack_lock:
            st = self.state
            if st == 0:
                self.state = 1  # sync ack: loop sees it, never arms
                return
            if st != 2:
                return  # double ack: no-op (same contract as _up)
            self.state = 3
            dec._pending -= 1
        dec._resume()


def _drain_blob(blob: BlobReader, done: Callable[[], None]) -> None:
    """Default blob handler: consume and discard (reference: decode.js:58-61).

    The discarding data callback matters: without one, BlobReader buffers
    every chunk for later replay and an unconsumed blob accumulates whole
    in host RAM — the opposite of draining.
    """
    blob.on_data(lambda _chunk: None)
    blob.on_end(done)


class Decoder:
    """Push-based incremental wire parser. See module docstring."""

    # the wire cost plane's link label (ISSUE 20): owners carrying more
    # than one session overwrite it per instance (the sidecar names it
    # after the session key) — a collector label, runtime by design
    cost_link = "session"

    def __init__(self):
        self.bytes = 0
        self.changes = 0
        self.blobs = 0
        self.destroyed = False
        self.finished = False
        self._on_change: Callable[[Change, Callable[[], None]], None] | None = None
        self._on_change_batch = None  # whole-batch columnar handler
        self._on_reconcile = None  # reconcile protocol message handler
        self._on_snapshot = None  # snapshot protocol message handler
        # reconcile/snapshot frames delivered: ride _frames_delivered
        # (neither touches the change-row counters)
        self.reconcile_frames = 0
        self.snapshot_frames = 0
        self._on_blob: Callable[[BlobReader, Callable[[], None]], None] | None = None
        self._on_finalize: Callable[[Callable[[], None]], None] | None = None
        self._error_cbs: list[Callable[[Exception | None], None]] = []
        self._finish_cbs: list[Callable[[], None]] = []

        # parser state
        self._state = TYPE_HEADER
        self._header = bytearray()  # accumulating varint+id bytes
        self._missing = 0  # payload bytes still to consume
        self._payload_parts: list[bytes] | None = None  # change slow path
        self._current_blob: BlobReader | None = None
        # wire-position cursor for causal tracing (obs/tracing.py):
        # _parsed counts wire bytes the parser fully consumed (bytes
        # holds ACCEPTED bytes, which includes unparsed overflow);
        # _frame_start is the wire offset of the frame being parsed —
        # the same number the sender's encoder tagged this frame with.
        # Maintained unconditionally (trivial int adds) so the offsets
        # stay coherent across mid-session gate flips; the bulk path
        # tracks its own base and re-syncs _parsed when a run retires.
        self._parsed = 0
        self._frame_start = 0
        # wire offset of the last exported checkpoint (fleet-plane
        # watermark: the resume point a reconnect would pay back to)
        self._ckpt_offset = 0

        # flow control
        self._pending = 0
        self._paused_readers = 0
        self._overflow: deque[memoryview] = deque()  # unparsed input, in order
        self._overflow_bytes = 0  # running total (kept in sync with the deque)
        self._bulk: dict | None = None  # parked native frame-index cursor
        # parked ChangeBatch delivery cursor: a batch frame whose rows
        # could not all dispatch (async ack / pause) resumes here —
        # ordering: nothing after the batch dispatches until it drains
        self._pbatch: dict | None = None
        # batch-frame accounting so _frames_delivered keeps counting
        # FRAMES while self.changes counts ROWS (a batch is one frame)
        self._batch_rows_seen = 0
        self._batch_frames_done = 0
        self._write_cbs: list[Callable[[], None]] = []
        self._end_queued = False
        self._end_cb: OnDone = None
        self._consuming = False  # reentrancy guard for _consume
        # drain watchers: persistent callbacks fired whenever a stall
        # clears (or the decoder dies), so a transport pump parked on
        # "not writable" wakes immediately on a cross-thread ack instead
        # of rediscovering the state on a poll (transport.recv_over)
        self._drain_watchers: list[Callable[[], None]] = []
        # serializes _FastAck state transitions against cross-thread acks
        self._ack_lock = threading.Lock()
        # dat_fastpath AckBoard (outstanding C-side armed acks), created
        # lazily the first time the C dispatch loop runs
        self._ack_board = None

    # -- handler registration (same shape as the reference API) -------------

    def change(self, cb: Callable[[Change, Callable[[], None]], None]) -> "Decoder":
        self._on_change = cb
        return self

    def reconcile(self, cb) -> "Decoder":
        """Register the reconcile-message handler: ``cb(msg, done)``
        receives each ``TYPE_RECONCILE`` frame's decoded
        :class:`~..wire.reconcile_codec.ReconcileMsg` and one ``done``
        per frame (the reconcile driver's receive surface).  Without a
        handler, reconcile frames are dropped — the same
        never-deadlock default as unhandled changes."""
        self._on_reconcile = cb
        return self

    def snapshot(self, cb) -> "Decoder":
        """Register the snapshot-message handler: ``cb(msg, done)``
        receives each ``TYPE_SNAPSHOT`` frame's decoded
        :class:`~..wire.snapshot_codec.SnapshotMsg` and one ``done``
        per frame (the snapshot driver's receive surface).  Without a
        handler, snapshot frames are dropped — the same never-deadlock
        default as unhandled changes."""
        self._on_snapshot = cb
        return self

    def change_batch(self, cb) -> "Decoder":
        """Register a whole-batch handler: ``cb(cols, done)`` receives a
        negotiated ``ChangeBatch`` frame's decoded columns (a
        :class:`~..runtime.replay.ChangeColumns`: ``len()`` rows,
        ``row(i)`` lazy materialization, numpy columns for bulk work)
        and ONE ``done`` for the whole frame — zero per-row Python on
        the decode side.  Without this handler, batch rows are delivered
        through the per-record :meth:`change` handler one
        :class:`Change` at a time (same observable stream as a
        per-record peer).  Per-record frames always go to
        :meth:`change`."""
        self._on_change_batch = cb
        return self

    @staticmethod
    def capabilities() -> int:
        """The capability mask this decoder can parse — what a receiver
        advertises during session setup (WIRE.md "Capability
        negotiation")."""
        return LOCAL_CAPS

    def blob(self, cb: Callable[[BlobReader, Callable[[], None]], None]) -> "Decoder":
        self._on_blob = cb
        return self

    def finalize(self, cb: Callable[[Callable[[], None]], None]) -> "Decoder":
        self._on_finalize = cb
        return self

    def on_error(self, cb: Callable[[Exception | None], None]) -> "Decoder":
        self._error_cbs.append(cb)
        return self

    def on_finish(self, cb: Callable[[], None]) -> "Decoder":
        if self.finished:
            cb()
        else:
            self._finish_cbs.append(cb)
        return self

    # -- write side ---------------------------------------------------------

    def write(self, data, on_consumed: OnDone = None) -> bool:
        """Feed wire bytes. Returns True if fully consumed synchronously;
        False if parsing stalled on an outstanding ``done`` (the
        ``on_consumed`` callback then fires when the app drains —
        reference: decode.js:124-133,168).

        The decoder may keep views of ``data`` past the call — unparsed
        input in its cursors, and a blob's payload as views until the
        blob's digest is dispatched (a digesting decoder with no blob
        handler registered copies a blob's bytes only into the staging
        row).  Hand it memory it may pin and nobody rewrites:
        immutable ``bytes``, or a buffer that is not written again
        (the pumps allocate a slab per receive for this)."""
        if self.destroyed:
            raise DecoderDestroyedError("write after destroy")
        if self.finished or self._end_queued:
            raise DecoderDestroyedError("write after end")
        data = memoryview(data.encode("utf-8") if isinstance(data, str) else data)
        self.bytes += len(data)
        if len(data):
            self._overflow.append(data)
            self._overflow_bytes += len(data)
        # Park the completion callback BEFORE consuming: _consume's
        # drained epilogue is the single place parked callbacks fire, so
        # a done() ack landing on another thread can never slip between
        # a stall check and the parking (the lost-wakeup TOCTOU).  A
        # fresh wrapper keeps the parked entry unique per call.
        entry = None
        if on_consumed is not None:
            entry = lambda cb=on_consumed: cb()  # noqa: E731
            self._write_cbs.append(entry)
        if _OBS.on:
            _M_DEC_BYTES.inc(len(data))
            t0 = _perf()
            try:
                self._consume()
            finally:
                _H_DEC_DISPATCH.observe(_perf() - t0)
        else:
            self._consume()
        if entry is not None:
            return entry not in self._write_cbs  # fired <=> consumed
        return not (
            self._overflow or self._bulk is not None
            or self._pbatch is not None or self._stalled()
        )

    def end(self, on_finished: OnDone = None) -> None:
        """Graceful end: after all prior frames are consumed, the finalize
        handler runs, then the session finishes (reference: decode.js:135-142)."""
        if self.destroyed:
            raise DecoderDestroyedError("end after destroy")
        if self._end_queued or self.finished:
            return
        self._end_queued = True
        self._end_cb = on_finished
        self._maybe_finalize()

    def destroy(self, err: Exception | None = None) -> None:
        """Fail-fast teardown, cascading to a live blob reader
        (reference: decode.js:104-110)."""
        if self.destroyed:
            return
        self.destroyed = True
        blob, self._current_blob = self._current_blob, None
        if blob is not None and not blob.destroyed:
            blob.destroyed = True
        self._overflow.clear()
        self._overflow_bytes = 0
        self._bulk = None
        self._pbatch = None
        for cb in self._error_cbs:
            cb(err)
        # Release parked write-completion callbacks so a transport blocked on
        # "consumed" wakes up and observes the destroyed state (Node errors
        # the pending Writable callback for the same reason).
        cbs, self._write_cbs = self._write_cbs, []
        for cb in cbs:
            cb()
        # ... and wake persistent drain watchers for the same reason
        self._notify_drain_watchers()

    def writable(self) -> bool:
        return not (
            self._stalled()
            or self._overflow
            or self._bulk is not None
            or self._pbatch is not None
            or self.destroyed
            or self.finished
        )

    def checkpoint(self, emit_event: bool = True):
        """Export this instant's session progress (resume support).

        Cheap and side-effect-free: a :class:`~.resume.SessionCheckpoint`
        whose ``wire_offset`` is the count of wire bytes this decoder has
        accepted — the exact byte a reconnecting sender must resume from
        (parser state, including mid-frame cursors and unparsed overflow,
        lives on in this object).  The frame/row/blob cursors and the
        backend digest state ride along for observability and structured
        error context.  See ROBUSTNESS.md.

        ``emit_event=False`` skips the ``session.checkpoint`` telemetry
        event: the flight recorder snapshots a checkpoint as bundle
        CONTEXT, and recording that as a checkpoint event would skew
        any analysis treating the event as "a resume point was taken".
        """
        from .resume import SessionCheckpoint

        self._ckpt_offset = self.bytes
        if emit_event and _OBS.on:
            _emit("session.checkpoint", wire_offset=self.bytes,
                  frame=self._frames_delivered(), row=self.changes)
        blob = self._current_blob
        return SessionCheckpoint(
            wire_offset=self.bytes,
            frame=self._frames_delivered(),
            row=self.changes,
            blob_offset=blob.received if blob is not None else 0,
            digest=self._checkpoint_digest(),
        )

    def watermark(self, link: str) -> None:
        """Export this decoder's wire-position cursors on the fleet
        plane (OBSERVABILITY.md "Fleet plane") under ``link``:
        ``accepted`` (bytes taken from the transport — the resume
        point), ``parsed`` (bytes the parser fully consumed — the lag
        join's receive frontier), and ``checkpoint`` (the last exported
        resume point).  All three already exist for resume/tracing;
        exporting them costs the hot path nothing — values are read
        only at snapshot time.  Call
        ``WATERMARKS.untrack(link)`` when the session ends."""
        _WATERMARKS.track("accepted", link, lambda: self.bytes)
        _WATERMARKS.track("parsed", link, lambda: self._parsed)
        _WATERMARKS.track("checkpoint", link, lambda: self._ckpt_offset)

    def _frames_delivered(self) -> int:
        """Frames fully delivered — the single frame-index authority for
        checkpoints AND structured error context (they must agree).
        ``blobs`` counts at OPEN (header time): a blob mid-payload is
        the frame being parsed, not a delivered one.  A ChangeBatch is
        ONE frame however many rows it carries: its rows are subtracted
        back out of ``changes`` and the frame counts once, at full
        delivery (mid-batch it is the frame being parsed, like a
        mid-payload blob).  A reconcile/snapshot frame counts once, at
        delivery, via its own counter."""
        return (self.changes - self._batch_rows_seen
                + self._batch_frames_done + self.blobs
                + self.reconcile_frames + self.snapshot_frames
                - (1 if self._current_blob is not None else 0))

    def _checkpoint_digest(self) -> dict:
        """Backend hook: running digest state to carry in a checkpoint
        (the TPU decoder records its emitted sequence counters).  Base:
        no digest surface, nothing to record."""
        return {}

    # -- drain watchers ------------------------------------------------------

    def _add_drain_watcher(self, cb: Callable[[], None]) -> None:
        """Register a persistent wakeup hook: fired (possibly from the
        acking thread) whenever parsing becomes unblocked, so a pump
        waiting on ``writable()`` can park on an event instead of
        polling.  Unlike ``write``'s one-shot ``on_consumed`` callbacks
        these survive across writes; remove with
        :meth:`_remove_drain_watcher`."""
        self._drain_watchers.append(cb)

    def _remove_drain_watcher(self, cb: Callable[[], None]) -> None:
        try:
            self._drain_watchers.remove(cb)
        except ValueError:
            pass

    def _notify_drain_watchers(self) -> None:
        for cb in list(self._drain_watchers):
            cb()

    def _protocol_error(self, message: str,
                        cause: BaseException | None = None) -> ProtocolError:
        """Structured wire error: every ProtocolError this decoder
        raises carries the frame index and byte offset where parsing
        stood — the session-context half of the robustness contract
        (ROBUSTNESS.md), so operators see *where* a stream broke instead
        of a bare message.

        This is also the flight recorder's primary hook (obs/flight.py):
        every decoder-side wire error funnels through here, so an armed
        recorder dumps its post-mortem bundle BEFORE destroy() clears
        the parser state the bundle narrates."""
        err = ProtocolError(
            message,
            frame=self._frames_delivered(),
            offset=self.bytes,
            cause=cause,
        )
        if _OBS.on:
            _M_DEC_ERRORS.inc()
            _emit("protocol.error", frame=err.frame, offset=err.offset,
                  message=message)
            self._lit_cost_failure(message)
        if _FLIGHT.armed:
            _FLIGHT.dump("protocol-error", error=err,
                         checkpoint=self.checkpoint(emit_event=False))
        return err

    # -- flow control --------------------------------------------------------

    def _stalled(self) -> bool:
        if self._pending > 0 or self._paused_readers > 0:
            return True
        board = self._ack_board
        return board is not None and board.outstanding > 0

    def _up(self) -> Callable[[], None]:
        """Create a one-shot ``done`` for an app callback; parsing pauses
        while any are outstanding (reference: decode.js:87-99)."""
        self._pending += 1
        fired = False

        def done() -> None:
            nonlocal fired
            if fired:
                return
            fired = True
            self._pending -= 1
            self._resume()

        return done

    def _resume(self) -> None:
        # While _consume is live on the stack, the outer loop may hold a
        # chunk's unparsed remainder in a local — it will keep going (pending
        # just dropped) and run the drained notifications itself, so a nested
        # resume must be a no-op rather than observe a falsely-empty overflow.
        if self.destroyed or self._stalled():
            return
        if self._drain_watchers:
            # fire BEFORE the _consuming check: when the outer loop is
            # live on another thread's stack, it may already be past its
            # own drained-epilogue — this notify is then the only wakeup
            # a parked pump gets (the lost-wakeup the transport's old
            # bounded poll papered over)
            self._notify_drain_watchers()
        if self._consuming:
            return
        self._consume()

    def _maybe_finalize(self) -> None:
        if (
            not self._end_queued
            or self.finished
            or self.destroyed
            or self._overflow
            or self._bulk is not None
            or self._pbatch is not None
            or self._stalled()
            or self._consuming  # drained-check at the end of _consume re-runs this
        ):
            return
        if self._state != TYPE_HEADER or self._header:
            self.destroy(self._protocol_error("stream ended mid-frame"))
            return
        self._end_queued = False  # run once

        def finish() -> None:
            self.finished = True
            cb, self._end_cb = self._end_cb, None
            if cb is not None:
                cb()
            cbs, self._finish_cbs = self._finish_cbs, []
            for fcb in cbs:
                fcb()

        if self._on_finalize is not None:
            self._on_finalize(finish)
        else:
            finish()

    # -- parser --------------------------------------------------------------

    # Subclass opt-in to the bulk fast loop: when True IN THE CLASS'S
    # OWN __dict__ (the gate reads cls.__dict__, so the opt-in does NOT
    # inherit), runs of change frames dispatch through
    # _dispatch_changes_fast even though _deliver_change is overridden,
    # and the raw payload of every dispatched change is handed to
    # _note_change_payloads afterwards (the digest decoder's tap).  The
    # contract: the declaring class's ONLY per-change addition is
    # handler-independent payload work; a subclass must re-declare the
    # flag to re-opt-in after auditing its own overrides.
    _bulk_payload_sink = False

    def _note_change_payloads(self, payloads, count: int) -> None:
        """Bulk-path tap: ``payloads`` is the in-order list of raw change
        payload bytes for the just-dispatched run (None when collection
        was off), ``count`` the number of changes dispatched.  Called
        after EVERY fast-loop run on sink-enabled subclasses — even with
        collection off — so sequence bookkeeping can advance.
        Base: no-op."""

    def _payload_sink_active(self) -> bool:
        """Whether the tap should actually COLLECT payloads (slicing
        costs per frame); sequence accounting happens either way."""
        return True

    # bulk path threshold: below this, the native round-trip (array
    # wrapping + index buffers) costs more than the per-byte scan saves.
    # 2048 measured (round 5): a transport writing ~4 KiB chunks leaves
    # a ~4000-byte remainder after the scanner crosses the straddling
    # frame — at the old 4096 threshold that remainder always rode the
    # scanner (5.5 MiB/s); at 2048 it re-enters the native index
    # (21.7 MiB/s), with large-write throughput unchanged (within noise)
    _NATIVE_MIN = 2048

    def _consume(self) -> None:
        """Main parse loop: drain overflow while the app is keeping up
        (reference: decode.js:144-169).

        When at least a buffer's worth of complete frames is queued and
        the parser sits at a frame boundary, the whole buffer is indexed
        in one native call (``dat_split_frames``,
        native/dat_native.cpp) and frames dispatch from the index —
        the reference's per-byte header scan (decode.js:251-262) drops
        out of the hot path entirely.  The per-byte scanner remains the
        slow/tail path: split headers, partial frames, tiny writes.

        Guarded against reentrancy: a handler that acks synchronously while
        the loop holds a chunk's unparsed remainder in a local must not
        re-enter and pop the *next* queued chunk out of order — the guard
        makes the nested resume a no-op and the outer loop carries on.
        """
        if self._consuming:
            return
        self._consuming = True
        try:
            while not self._stalled() and not self.destroyed:
                if self._pbatch is not None:
                    # resume a parked ChangeBatch dispatch from its row
                    # cursor — nothing else parses until it drains
                    # (frame order is delivery order)
                    self._run_pending_batch()
                    if self._pbatch is not None:
                        return  # still stalled mid-batch
                    continue
                if self._bulk is not None:
                    # resume a parked frame index from its cursor — an
                    # async ack must NOT re-index/re-decode the remainder
                    # (that would make bulk decode O(frames^2))
                    self._run_indexed()
                    continue
                if not self._overflow:
                    break
                if (
                    self._state == TYPE_HEADER
                    and not self._header
                    # O(1) size gate BEFORE merging: joining the backlog
                    # costs O(bytes), and when the native path is
                    # unavailable (_NATIVE_MIN pushed to 2**62) an
                    # unconditional merge would re-copy the whole backlog
                    # on every resume — quadratic on the Python fallback
                    and self._overflow_bytes >= self._NATIVE_MIN
                ):
                    merged = self._merged_overflow()
                    if merged is not None and len(merged) >= self._NATIVE_MIN:
                        if self._start_indexed(merged):
                            continue
                        if self.destroyed:
                            return
                        # no complete frame in the whole buffer (e.g. a
                        # large blob frame still arriving): fall through
                        # to the streaming scanner so it can enter the
                        # frame and consume payload incrementally
                        self._ov_appendleft(merged)
                    elif merged is not None:
                        self._ov_appendleft(merged)
                chunk = self._overflow.popleft()
                self._overflow_bytes -= len(chunk)
                rest = self._consume_chunk(chunk)
                if self.destroyed:
                    return
                if rest is not None and len(rest):
                    self._ov_appendleft(rest)
        finally:
            self._consuming = False
        # Fully drained and nothing outstanding: release parked writers and
        # run a queued finalization. This lives here (not in _resume) so a
        # handler acking synchronously mid-loop cannot finalize while the
        # loop still holds unparsed bytes in a local.
        if (
            not self.destroyed
            and not self._overflow
            and self._bulk is None
            and self._pbatch is None
            and not self._stalled()
        ):
            cbs, self._write_cbs = self._write_cbs, []
            for cb in cbs:
                cb()
            self._maybe_finalize()
            self._notify_drain_watchers()

    def _ov_appendleft(self, mv: memoryview) -> None:
        self._overflow.appendleft(mv)
        self._overflow_bytes += len(mv)

    def _requeue_tail(self, rest) -> None:
        """A handler raised while this chunk's unparsed remainder lived
        only in a delivery-site local: requeue it so a caught
        raise-then-resume continues with the NEXT frame instead of
        silently dropping every frame after the raising one in the same
        write (the streaming analogue of the bulk path's parked cursor,
        which preserves its tail in st)."""
        if len(rest):
            if _OBS.on:
                _M_DEC_REQUEUES.inc()
                _emit("decoder.requeue", bytes=len(rest),
                      offset=self.bytes)
            self._ov_appendleft(rest)

    def _merged_overflow(self) -> memoryview | None:
        """Pop ALL queued overflow as one contiguous memoryview."""
        if not self._overflow:
            return None
        if len(self._overflow) == 1:
            chunk = self._overflow.popleft()
            self._overflow_bytes -= len(chunk)
            return chunk
        chunks = list(self._overflow)
        self._overflow.clear()
        self._overflow_bytes = 0
        return memoryview(b"".join(chunks))

    def _start_indexed(self, buf: memoryview) -> bool:
        """Index ``buf``'s complete frames natively and park a cursor.

        One ``dat_split_frames`` call replaces per-frame header scans,
        and one ``dat_decode_changes`` call pre-decodes every change
        payload columnar-wise (the per-record Python proto parse is ~2/3
        of bulk decode time, measured).  The index + columns + cursor
        live in ``self._bulk`` so an async ack resumes dispatch where it
        stopped instead of re-indexing the remainder.

        Returns False when the bulk path cannot proceed (no native lib,
        or zero complete frames in the buffer) — the caller falls back
        to the streaming scanner.  On a corrupt change payload the
        columns are dropped and the per-frame Python decoder takes over,
        so records before the corrupt one are still delivered and the
        error surfaces with identical semantics.
        """
        from ..runtime import native

        lib = native.get_lib()
        if lib is None:
            self._NATIVE_MIN = 1 << 62  # don't retry every write
            return False
        import ctypes

        import numpy as np

        arr = np.frombuffer(buf, dtype=np.uint8)
        cap = len(arr) // 2 + 1  # a frame is at least 2 bytes
        starts = np.empty(cap, dtype=np.int64)
        lens = np.empty(cap, dtype=np.int64)
        ids = np.empty(cap, dtype=np.uint8)
        consumed = ctypes.c_int64(0)
        err = ctypes.c_int64(0)
        n = lib.dat_split_frames(arr, len(arr), starts, lens, ids, cap,
                                 ctypes.byref(consumed), ctypes.byref(err))
        # A malformed header mid-buffer only STOPS the native scan (err is
        # informational): the valid prefix still dispatches through the
        # bulk path and the streaming scanner re-encounters the bad
        # header in the remainder, destroying at exactly the frame the
        # per-byte path would — delivery-before-error must not depend on
        # how the transport chunked its writes.
        if n <= 0:
            return False
        self._install_index(buf, arr, starts, lens, ids, n,
                            int(consumed.value))
        return True

    def _install_index(self, buf, arr, starts, lens, ids, n: int,
                       consumed: int) -> None:
        """Park a frame index over ``buf`` as the bulk cursor — the
        shared installer behind :meth:`_start_indexed` (scan done here)
        and :meth:`write_indexed` (scan done inside the native pump's
        GIL-released receive call).  ``starts``/``lens``/``ids`` may be
        over-allocated; only ``[:n]`` is the index."""
        import ctypes

        import numpy as np

        from ..runtime import native

        lib = native.get_lib()
        cols_np = None
        cidx = np.nonzero(ids[:n] == TYPE_CHANGE)[0]
        m = len(cidx)
        if m >= 16 and lib is not None:
            chg = np.empty(m, np.uint32)
            frm = np.empty(m, np.uint32)
            tov = np.empty(m, np.uint32)
            koff = np.empty(m, np.int64)
            klen = np.empty(m, np.int64)
            soff = np.empty(m, np.int64)
            slen = np.empty(m, np.int64)
            voff = np.empty(m, np.int64)
            vlen = np.empty(m, np.int64)
            erri = ctypes.c_int64(-1)
            rc = lib.dat_decode_changes(
                arr, np.ascontiguousarray(starts[cidx]),
                np.ascontiguousarray(lens[cidx]), m,
                chg, frm, tov, koff, klen, soff, slen, voff, vlen,
                ctypes.byref(erri),
            )
            if rc == 0:
                # kept as the raw numpy columns: the C dispatch loop
                # reads the buffers directly; the Python loops get
                # list/tuple views lazily (_cols_lists) — converting
                # eagerly cost ~0.5us/frame of tolist/zip
                cols_np = (chg, frm, tov, koff, klen, soff, slen,
                           voff, vlen)
        self._bulk = {
            "buf": buf,
            # wire offset of buf[0]: the indexed buffer is exactly the
            # unconsumed overflow, so it starts where parsing stood
            "base": self._parsed,
            "starts": starts[:n].tolist(),
            "lens": lens[:n].tolist(),
            "ids": ids[:n].tolist(),
            "ids_np": np.ascontiguousarray(ids[:n]),
            "starts_np": np.ascontiguousarray(starts[:n]),
            "lens_np": np.ascontiguousarray(lens[:n]),
            "n": n,
            "consumed": consumed,
            "f": 0,
            "row": 0,
            "cols_np": cols_np,
            "blob_open": False,
        }

    def write_indexed(self, data, starts, lens, ids, n: int,
                      consumed: int) -> bool:
        """Feed wire bytes WITH a pre-computed native frame index — the
        transport pump's bulk entry (session/pump.py): the pump's
        GIL-released receive call already ran ``dat_split_frames`` over
        ``data``, so the index installs directly instead of re-scanning.
        Return contract matches :meth:`write` (True = fully consumed
        synchronously).

        Only valid at a clean frame boundary with nothing parked; any
        other parser state falls back to :meth:`write` (the index is
        then recomputed if the merged backlog qualifies) — byte-stream
        semantics are identical either way, this entry only skips
        redundant work."""
        if (n <= 0 or self._overflow or self._bulk is not None
                or self._pbatch is not None or self._state != TYPE_HEADER
                or self._header or self._consuming or self._stalled()):
            return self.write(data)
        if self.destroyed:
            raise DecoderDestroyedError("write after destroy")
        if self.finished or self._end_queued:
            raise DecoderDestroyedError("write after end")
        import numpy as np

        buf = memoryview(data)
        self.bytes += len(buf)
        self._install_index(buf, np.frombuffer(buf, dtype=np.uint8),
                            starts, lens, ids, n, consumed)
        if _OBS.on:
            _M_DEC_BYTES.inc(len(buf))
            t0 = _perf()
            try:
                self._consume()
            finally:
                _H_DEC_DISPATCH.observe(_perf() - t0)
        else:
            self._consume()
        return not (
            self._overflow or self._bulk is not None
            or self._pbatch is not None or self._stalled()
        )

    @staticmethod
    def _cols_lists(st: dict):
        """Python-loop view of the columnar decode: one tuple per row
        (lazy; the C dispatcher never needs it)."""
        rows = st.get("zrows")
        if rows is None and st["cols_np"] is not None:
            rows = st["zrows"] = list(
                zip(*(a.tolist() for a in st["cols_np"]))
            )
        return rows

    def _run_indexed(self) -> None:
        """Dispatch frames from the parked index until done or stalled.

        Each frame goes through the same change/blob machinery as the
        streaming path (counters, ordering, blob latches, zero-length
        blobs — shared, not duplicated).  Runs of consecutive change
        frames take :meth:`_dispatch_changes_fast` when the columnar
        pre-decode is available and ``_deliver_change`` is not
        subclassed — same observable contract, ~3x less per-frame
        interpreter work (the config-1 decode rate rides this loop).
        """
        st = self._bulk
        assert st is not None
        buf = st["buf"]
        starts, lens, ids = st["starts"], st["lens"], st["ids"]
        have_cols = st["cols_np"] is not None
        rows_l = self._cols_lists(st) if have_cols else None
        f = st["f"]
        row = st["row"]
        n = st["n"]
        cls = type(self)
        # the sink opt-in is deliberately NON-inheritable (__dict__, not
        # attribute lookup): a subclass overriding _deliver_change would
        # otherwise silently lose its override on bulk writes while
        # keeping it on chunked ones
        fast = (have_cols
                and (cls._deliver_change is Decoder._deliver_change
                     or cls.__dict__.get("_bulk_payload_sink", False)))
        try:
            while f < n:
                if self._stalled() or self.destroyed:
                    return
                type_id = ids[f]
                if fast and type_id == TYPE_CHANGE:
                    try:
                        # return value deliberately unused: the st
                        # write-back is the one cursor-handoff channel
                        # (it is what survives handler raises)
                        self._dispatch_changes_fast(st, f)
                    finally:
                        # the fast loops (C and Python) write BOTH
                        # cursors into st — on their raise path too;
                        # resync the locals so the outer finally below
                        # cannot clobber st with stale values
                        f, row = st["f"], st["row"]
                    if self.destroyed:
                        self._bulk = None
                        return
                    continue
                start = starts[f]
                flen = lens[f]
                self._missing = flen
                # the frame's wire start offset (starts[] points at the
                # payload AFTER the id byte; back out the header) — the
                # tracing tag both _deliver_change and the blob open
                # read; unconditional so offsets stay coherent across
                # gate flips mid-run
                self._frame_start = st["base"] + start - _header_len(flen)
                if type_id == TYPE_CHANGE:
                    if have_cols:
                        (cg, fr, to, ko, kl, so, sl, vo, vl) = rows_l[row]
                        if self._on_change is not None:
                            try:
                                change = Change(
                                    key=str(buf[ko : ko + kl], "utf-8"),
                                    change=cg,
                                    from_=fr,
                                    to=to,
                                    value=(bytes(buf[vo : vo + vl])
                                           if vl >= 0 else b""),
                                    subset=(str(buf[so : so + sl], "utf-8")
                                            if sl >= 0 else ""),
                                )
                            except ValueError as e:  # incl. UnicodeDecodeError
                                self._bulk = None
                                self.destroy(self._protocol_error(str(e), cause=e))
                                return
                        else:
                            # no registered handler will ever see the object
                            # (the default drops changes) — but the payload
                            # must still be VALID: the key's UTF-8 check is
                            # the one observable part of construction, and a
                            # digest-only subclass (TpuDecoder with no change
                            # handler — the sidecar's shape) still needs the
                            # wire error.  ``change=None`` is a documented
                            # private contract of _deliver_change.
                            try:
                                str(buf[ko : ko + kl], "utf-8")
                                if sl >= 0:
                                    str(buf[so : so + sl], "utf-8")
                            except ValueError as e:
                                self._bulk = None
                                self.destroy(self._protocol_error(str(e), cause=e))
                                return
                            change = None
                        # delivery consumes the frame: advance BOTH
                        # cursor halves before the handler can raise —
                        # the finally below persists them together, so
                        # a raise-then-resume re-enters at the next
                        # frame with row still paired to it
                        row += 1
                        f += 1
                        self._missing = 0
                        self._deliver_change(change, buf[start : start + flen])
                    else:
                        row += 1
                        f += 1
                        self._state = TYPE_CHANGE
                        self._payload_parts = None
                        self._change_data(buf[start : start + flen])
                elif type_id == TYPE_CHANGE_BATCH:
                    # delivery consumes the frame (the change/blob
                    # doctrine): advance BEFORE dispatch so a handler
                    # raise resumes at the next frame; an async ack
                    # parks the ROW cursor in _pbatch, and _consume
                    # drains it before touching this index again
                    f += 1
                    self._missing = 0
                    self._finish_change_batch(buf[start : start + flen])
                    if self.destroyed:
                        self._bulk = None
                        return
                    if self._pbatch is not None or self._stalled():
                        return
                elif type_id == TYPE_RECONCILE:
                    # same advance-before-dispatch doctrine; delivery is
                    # whole-frame, so only a stall can park the index
                    f += 1
                    self._missing = 0
                    self._finish_reconcile(buf[start : start + flen])
                    if self.destroyed:
                        self._bulk = None
                        return
                    if self._stalled():
                        return
                elif type_id == TYPE_SNAPSHOT:
                    # same whole-frame doctrine as reconcile
                    f += 1
                    self._missing = 0
                    self._finish_snapshot(buf[start : start + flen])
                    if self.destroyed:
                        self._bulk = None
                        return
                    if self._stalled():
                        return
                elif type_id == TYPE_BLOB:
                    if not st["blob_open"]:
                        self._state = TYPE_BLOB
                        self._current_blob = None
                        # opened-state advances WITH the side effect: a
                        # blob handler that raises must not re-open (and
                        # re-count) the same blob on resume
                        st["blob_open"] = True
                        self._open_blob_if_ready()
                        if self.destroyed:
                            self._bulk = None
                            return
                        # a handler that pause()d synchronously must not
                        # receive the payload until it resumes — same as
                        # the streaming path parking the chunk undelivered
                        if flen and self._stalled():
                            return
                    # delivery consumes the frame (same doctrine as the
                    # change path above): advance BEFORE the reader
                    # callbacks can raise, so a caught raise-then-resume
                    # continues at the next frame instead of
                    # re-delivering (and re-digesting) this payload
                    st["blob_open"] = False
                    f += 1
                    if flen:
                        self._blob_data(buf[start : start + flen])
                else:
                    self._bulk = None
                    self.destroy(
                        self._protocol_error(
                            f"Protocol error, unknown type: {type_id}")
                    )
                    return
                if self.destroyed:
                    self._bulk = None
                    return
        finally:
            # single atomic write-back for every exit — returns, handler
            # exceptions, stalls: the cursor halves leave together or
            # not at all (st is dead when _bulk was dropped; the write
            # is then harmless)
            st["f"] = f
            st["row"] = row
        self._bulk = None
        # run retired: re-sync the wire-position cursor to the exact
        # bytes the index covered (interim _blob_data/_change_data adds
        # during the run were provisional; this SET is authoritative)
        self._parsed = st["base"] + st["consumed"]
        tail = buf[st["consumed"]:]
        if len(tail):
            self._ov_appendleft(tail)

    def _dispatch_changes_fast(self, st: dict, f: int) -> int:
        """Deliver the run of consecutive change frames starting at ``f``.

        The hot loop of config-1 bulk decode.  Per frame: one slot-built
        :class:`Change` from the pre-decoded columns, one
        :class:`_FastAck`, one handler call — no ``_up`` closure, no
        pending-counter churn unless the handler actually defers its
        ack, no per-frame parser-state writes (the whole run happens at
        a frame boundary, so ``_state`` stays ``TYPE_HEADER``
        throughout).  Slices come from a one-time ``bytes`` copy of the
        indexed buffer: bytes slicing + decoding is ~2x cheaper than
        going through memoryview objects.

        Returns the index of the first undispatched frame (a non-change
        frame, a stall, or ``n``).  Counters and cursor semantics are
        identical to the general loop; ``self.changes`` is incremented
        before each handler call exactly as ``_deliver_change`` does.
        """
        use_tap = type(self).__dict__.get("_bulk_payload_sink", False)
        collect = use_tap and self._payload_sink_active()
        row0 = st["row"]
        f0 = f
        fp = _fastpath_mod()
        if fp is not None:
            if self._ack_board is None:
                self._ack_board = fp.AckBoard()
            sink = [] if collect else None
            try:
                # handler exceptions propagate from here as themselves
                # (the C loop reports WIRE decode errors via status 2,
                # never as an exception — a handler-raised ValueError
                # must not be misread as a protocol error)
                f, _row, status = fp.dispatch_changes(
                    self, self._ack_board, self._on_change,
                    Change, st["buf"], st["ids_np"], *st["cols_np"],
                    f, st["row"], st["n"], st,
                    st["starts_np"] if collect else None,
                    st["lens_np"] if collect else None,
                    sink,
                )
            finally:
                # the C loop runs at a frame boundary throughout (same
                # invariant as the Python loop's finally below); the
                # sink drains even when a handler raised — those
                # changes WERE delivered, so their digests are owed
                # (matching the streaming path's submit-before-deliver)
                self._missing = 0
                self._state = TYPE_HEADER
                if _OBS.on and st["row"] > row0:
                    _M_DEC_CHANGES.inc(st["row"] - row0)
                    # one run-level tag for the whole C dispatch (the
                    # native loop cannot tag per frame): covers the
                    # contiguous wire range of the dispatched frames
                    k = st["f"] - f0
                    if k > 0:
                        fs0, fl0 = st["starts"][f0], st["lens"][f0]
                        last = f0 + k - 1
                        off0 = st["base"] + fs0 - _header_len(fl0)
                        end = st["base"] + st["starts"][last] \
                            + st["lens"][last]
                        if _OBS.frames:
                            _trace_instant("decoder.frame.run", offset=off0,
                                           kind="change", frames=k,
                                           wire_len=end - off0)
                        self._lit_cost_change_run(
                            end - off0, sum(st["lens"][f0:f0 + k]), k)
                if use_tap:
                    self._note_change_payloads(sink, st["row"] - row0)
            if status == 2:
                self.destroy(self._protocol_error(
                    st.pop("decode_error", "invalid change payload")))
            return f

        bbuf = st.get("bbuf")
        if bbuf is None:
            bbuf = st["bbuf"] = bytes(st["buf"])
        rows = self._cols_lists(st)
        ids = st["ids"]
        fstarts = st["starts"]
        flens = st["lens"]
        sink = [] if collect else None
        n = st["n"]
        row = st["row"]
        on_change = self._on_change
        lock = self._ack_lock
        obs_on = _OBS.on  # hoisted: one load for the whole run
        frames_on = obs_on and _OBS.frames  # the per-frame instants
        base = st["base"]
        mk = Change.__new__
        mka = _FastAck.__new__
        Ch = Change
        FA = _FastAck
        TC = TYPE_CHANGE
        try:
            while f < n and ids[f] == TC:
                (cg, fr, to, ko, kl, so, sl, vo, vl) = rows[row]
                try:
                    c = mk(Ch)
                    c.key = bbuf[ko : ko + kl].decode("utf-8")
                    c.change = cg
                    c.from_ = fr
                    c.to = to
                    c.value = bbuf[vo : vo + vl] if vl >= 0 else b""
                    c.subset = (bbuf[so : so + sl].decode("utf-8")
                                if sl >= 0 else "")
                except ValueError as e:  # incl. UnicodeDecodeError
                    self.destroy(self._protocol_error(str(e), cause=e))
                    return f
                if sink is not None:  # valid frame: its digest is owed
                    fs = fstarts[f]
                    sink.append(bbuf[fs : fs + flens[f]])
                row += 1
                f += 1
                self.changes += 1
                if frames_on:
                    fl = flens[f - 1]
                    hl = _header_len(fl)
                    _trace_instant("decoder.frame",
                                   offset=base + fstarts[f - 1] - hl,
                                   kind="change", wire_len=hl + fl)
                if on_change is not None:
                    ack = mka(FA)
                    ack.dec = self
                    ack.state = 0
                    on_change(c, ack)
                    if ack.state != 1:
                        with lock:
                            if ack.state == 0:
                                ack.state = 2  # armed: handler went async
                                self._pending += 1
                    # default: drop (reference: decode.js:54-56)
                if self.destroyed or self._pending > 0 \
                        or self._paused_readers > 0:
                    return f
        finally:
            # BOTH cursor halves, atomically — matching the C loop's
            # unconditional write-back: a handler that raises after
            # row/f advanced must leave them advanced together, or the
            # resume re-pairs frame payloads with the wrong rows
            # (round-5 advisor, high)
            st["f"] = f
            st["row"] = row
            self._missing = 0
            self._state = TYPE_HEADER
            if _OBS.on and row > row0:
                _M_DEC_CHANGES.inc(row - row0)
                ptot = sum(flens[f0:f])
                self._lit_cost_change_run(
                    ptot + sum(_header_len(x) for x in flens[f0:f]),
                    ptot, f - f0)
            if use_tap:
                self._note_change_payloads(sink, row - row0)
        return f

    def _consume_chunk(self, chunk: memoryview) -> memoryview | None:
        if self._state == TYPE_HEADER:
            return self._scan_header(chunk)
        if self._state == TYPE_CHANGE:
            return self._change_data(chunk)
        if self._state == TYPE_BLOB:
            return self._blob_data(chunk)
        if self._state == TYPE_CHANGE_BATCH:
            return self._batch_data(chunk)
        if self._state == TYPE_RECONCILE:
            return self._reconcile_data(chunk)
        if self._state == TYPE_SNAPSHOT:
            return self._snapshot_data(chunk)
        raise AssertionError(f"bad parser state {self._state}")

    def _scan_header(self, chunk: memoryview) -> memoryview | None:
        """Byte-at-a-time varint scan; the byte after the varint is the type
        id (reference: decode.js:251-262). Bounded at MAX_HEADER_LEN."""
        i = 0
        n = len(chunk)
        while i < n:
            self._header.append(chunk[i])
            i += 1
            # varint terminated iff the *previous* byte had its MSB clear and
            # we now also hold the id byte.
            if len(self._header) >= 2 and not (self._header[-2] & 0x80):
                hdr_len = len(self._header)
                self._parsed += i
                # this frame's wire start: where its first header byte
                # was consumed (the causal key both peers share)
                self._frame_start = self._parsed - hdr_len
                try:
                    framed_len, _ = decode_uvarint(self._header)
                except ValueError as e:  # e.g. varint exceeds 64 bits
                    self.destroy(self._protocol_error(str(e), cause=e))
                    return None
                type_id = self._header[-1]
                self._header.clear()
                self._missing = framed_len - 1  # length counts the id byte
                if framed_len < 1:
                    self.destroy(self._protocol_error("frame length must be >= 1"))
                    return None
                if type_id == TYPE_CHANGE:
                    self._state = TYPE_CHANGE
                    self._payload_parts = None
                elif type_id == TYPE_CHANGE_BATCH:
                    self._state = TYPE_CHANGE_BATCH
                    self._payload_parts = None
                elif type_id == TYPE_RECONCILE:
                    self._state = TYPE_RECONCILE
                    self._payload_parts = None
                elif type_id == TYPE_SNAPSHOT:
                    self._state = TYPE_SNAPSHOT
                    self._payload_parts = None
                elif type_id == TYPE_BLOB:
                    self._state = TYPE_BLOB
                    self._current_blob = None
                    try:
                        self._open_blob_if_ready()
                    except BaseException:
                        # handler raise: the chunk's remaining bytes are
                        # only in this local — requeue them or a caught
                        # raise-then-resume silently loses every frame
                        # after this one in the same write
                        self._requeue_tail(chunk[i:])
                        raise
                else:
                    self.destroy(
                        self._protocol_error(
                            f"Protocol error, unknown type: {type_id}")
                    )
                    return None
                return chunk[i:]
            if len(self._header) >= MAX_HEADER_LEN:
                self._parsed += i
                self.destroy(self._protocol_error("frame header too long"))
                return None
        self._parsed += n  # header still accumulating across chunks
        return None

    # -- change frames -------------------------------------------------------

    def _change_data(self, chunk: memoryview) -> memoryview | None:
        if self._payload_parts is None and len(chunk) >= self._missing:
            # fast path: whole payload inside one chunk — zero-copy slice
            # (reference: decode.js:217-227)
            payload = chunk[: self._missing]
            rest = chunk[self._missing :]
            self._parsed += self._missing
            self._missing = 0
            try:
                self._finish_change(payload)
            except BaseException:
                self._requeue_tail(rest)  # handler raise: keep the tail
                raise
            return rest
        # slow path: accumulate across chunk boundaries (reference:
        # decode.js:229-248)
        if self._payload_parts is None:
            self._payload_parts = []
        take = min(len(chunk), self._missing)
        self._payload_parts.append(bytes(chunk[:take]))
        self._parsed += take
        self._missing -= take
        rest = chunk[take:]
        if self._missing == 0:
            parts, self._payload_parts = self._payload_parts, None
            try:
                self._finish_change(b"".join(parts))
            except BaseException:
                self._requeue_tail(rest)  # handler raise: keep the tail
                raise
        return rest

    # -- wire cost lit helpers (ISSUE 20) ------------------------------------
    # Each hot path forks ONCE on `_OBS.on`; the helper below the fork
    # holds every wirecost symbol, so the dark twin's bytecode provably
    # references none of them (tests/test_wirecost.py asserts it) and
    # the disabled cost stays one attribute load.  The frame CLASS is a
    # string literal at every call (the datlint obs-discipline
    # contract).

    def _lit_cost_change(self, plen: int) -> None:
        _wirecost.account("change", self.cost_link, "rx", plen,
                          _header_len(plen))

    def _lit_cost_change_run(self, wire_total: int, payload_total: int,
                             frames: int) -> None:
        _wirecost.account("change", self.cost_link, "rx", payload_total,
                          wire_total - payload_total, frames)

    def _lit_cost_batch(self, plen: int, cols, rows: int) -> None:
        from ..wire import batch_codec

        hl = _header_len(plen)
        _wirecost.account("change_batch", self.cost_link, "rx", plen, hl)
        # satellite: the receiver prices the batch savings with the SAME
        # exact arithmetic the encoder ran pre-encode — decoded column
        # lengths feed the identical per-record estimate, so the two
        # counters agree to the byte
        est = batch_codec.estimate_per_record_bytes(
            cols.key_len, cols.sub_len, cols.val_len,
            cols.change, cols.from_, cols.to)
        saved = int(est) - (hl + plen)
        if saved > 0:
            _M_BATCH_SAVED_RX.inc(saved)
            _wirecost.note_saved(self.cost_link, "rx", saved)

    def _lit_cost_reconcile(self, plen: int) -> None:
        _wirecost.account("reconcile", self.cost_link, "rx", plen,
                          _header_len(plen))

    def _lit_cost_snapshot(self, plen: int) -> None:
        _wirecost.account("snapshot", self.cost_link, "rx", plen,
                          _header_len(plen))

    def _lit_cost_blob(self, length: int) -> None:
        # accrued in full at frame open — the same moment the
        # decoder.frame tag prices the whole frame
        _wirecost.account("blob", self.cost_link, "rx", length,
                          _header_len(length))

    def _lit_cost_failure(self, message: str) -> None:
        # a wire fault: the ledger keeps its last watermarks (the cost
        # did not heal) — only the failure counter moves
        _wirecost.note_failure(self.cost_link, "rx", message)

    def _finish_change(self, payload) -> None:
        try:
            change = decode_change(payload)
        except ValueError as e:
            self.destroy(self._protocol_error(str(e), cause=e))
            return
        self._deliver_change(change, payload)

    def _deliver_change(self, change: Change | None, payload) -> None:
        """Deliver one decoded change: the single hook both parse paths
        (streaming scanner and native bulk index) funnel through, so
        subclasses adding per-change work (the TPU backend hashes every
        payload) override exactly one method.

        Private contract: ``change`` may be ``None`` ONLY when no change
        handler is registered (``self._on_change is None``) — the bulk
        loop skips dead object construction then.  Subclasses must use
        ``payload``, not ``change``, for handler-independent work."""
        self.changes += 1
        if _OBS.on:
            _M_DEC_CHANGES.inc()
            if _OBS.frames:
                _trace_instant("decoder.frame", offset=self._frame_start,
                               kind="change",
                               wire_len=_header_len(len(payload))
                               + len(payload))
            self._lit_cost_change(len(payload))
        self._state = TYPE_HEADER
        if self._on_change is not None:
            # same deferred-arm ack as the bulk fast loop: a sync ack
            # (the common case) never touches the pending counter, and
            # the lock arbitrates the cross-thread handler-returned vs
            # done() race exactly as there
            ack = _FastAck(self)
            self._on_change(change, ack)
            if ack.state != 1:
                with self._ack_lock:
                    if ack.state == 0:
                        ack.state = 2  # armed: handler went async
                        self._pending += 1
        # default: drop (reference: decode.js:54-56)

    # -- ChangeBatch frames --------------------------------------------------

    def _sized_payload_data(self, chunk: memoryview,
                            finish) -> memoryview | None:
        """Accumulate one whole-payload frame across transport chunks
        and hand the complete payload to ``finish`` — the shared
        parse/requeue discipline of ChangeBatch and reconcile frames
        (same slicing as :meth:`_change_data`, which keeps its own copy:
        per-record changes are the hot path and must not pay a callback
        indirection per frame)."""
        if self._payload_parts is None and len(chunk) >= self._missing:
            payload = chunk[: self._missing]
            rest = chunk[self._missing :]
            self._parsed += self._missing
            self._missing = 0
            try:
                finish(payload)
            except BaseException:
                self._requeue_tail(rest)  # handler raise: keep the tail
                raise
            return rest
        if self._payload_parts is None:
            self._payload_parts = []
        take = min(len(chunk), self._missing)
        self._payload_parts.append(bytes(chunk[:take]))
        self._parsed += take
        self._missing -= take
        rest = chunk[take:]
        if self._missing == 0:
            parts, self._payload_parts = self._payload_parts, None
            try:
                finish(b"".join(parts))
            except BaseException:
                self._requeue_tail(rest)  # handler raise: keep the tail
                raise
        return rest

    def _batch_data(self, chunk: memoryview) -> memoryview | None:
        return self._sized_payload_data(chunk, self._finish_change_batch)

    def _finish_change_batch(self, payload) -> None:
        """Decode one complete ChangeBatch payload and start dispatching
        its rows.  Decode is pure array reinterpretation
        (wire/batch_codec.py) — a structurally corrupt payload (bad
        width, truncated column, out-of-range index, non-UTF-8
        dictionary) destroys the session with a ProtocolError exactly
        like a corrupt per-record Change payload."""
        from ..wire import batch_codec

        try:
            cols = batch_codec.decode_change_batch(payload)
        except ValueError as e:
            self.destroy(self._protocol_error(str(e), cause=e))
            return
        n = len(cols.change)
        if _OBS.on:
            _M_DEC_BATCH_FRAMES.inc()
            if _OBS.frames:
                _trace_instant("decoder.frame", offset=self._frame_start,
                               kind="change_batch", rows=n,
                               wire_len=_header_len(len(payload))
                               + len(payload))
            self._lit_cost_batch(len(payload), cols, n)
        self._state = TYPE_HEADER
        # digest tap: the whole frame's rows are owed at acceptance (the
        # blob doctrine — one frame, one accounting point), BEFORE any
        # row reaches a handler, keeping submit order = wire order
        self._note_change_batch(cols, n)
        self._pbatch = {"cols": cols, "row": 0, "n": n, "bbuf": None}
        self._run_pending_batch()

    def _note_change_batch(self, cols, n: int) -> None:
        """Hook: one call per accepted ChangeBatch frame with its decoded
        columns, before row dispatch (the digest decoder re-encodes rows
        canonically and submits their digests here).  Base: no-op."""

    def _run_pending_batch(self) -> None:
        """Dispatch rows from the parked batch cursor until done or
        stalled — the per-row half of batch delivery, only as fast as
        the registered handler shape allows (a ``change_batch`` handler
        takes the columns whole; a per-record ``change`` handler gets
        one slot-built :class:`Change` per row, same contract as the
        bulk fast loop)."""
        pb = self._pbatch
        assert pb is not None
        cols = pb["cols"]
        n = pb["n"]
        row = pb["row"]
        on_batch = self._on_change_batch
        if on_batch is not None and row == 0:
            # whole-batch delivery: one handler call, one ack
            self._pbatch = None
            self.changes += n
            self._batch_rows_seen += n
            self._batch_frames_done += 1
            if _OBS.on:
                _M_DEC_CHANGES.inc(n)
            ack = _FastAck(self)
            on_batch(cols, ack)
            if ack.state != 1:
                with self._ack_lock:
                    if ack.state == 0:
                        ack.state = 2  # armed: handler went async
                        self._pending += 1
            return
        on_change = self._on_change
        if on_change is None:
            # no handler: rows drop (reference: decode.js:54-56); the
            # payload was already structurally validated at decode
            k = n - row
            self._pbatch = None
            self.changes += k
            self._batch_rows_seen += k
            self._batch_frames_done += 1
            if _OBS.on and k:
                _M_DEC_CHANGES.inc(k)
            return
        bbuf = pb["bbuf"]
        if bbuf is None:
            # one bytes materialization per batch: bytes slicing +
            # decoding beats going through memoryview objects (same
            # measurement as the bulk fast loop's bbuf)
            bbuf = pb["bbuf"] = cols.buf.tobytes()
        ko, kl = cols.key_off, cols.key_len
        so, sl = cols.sub_off, cols.sub_len
        vo, vl = cols.val_off, cols.val_len
        cg, fr, tv = cols.change, cols.from_, cols.to
        row0 = row
        lock = self._ack_lock
        mk = Change.__new__
        mka = _FastAck.__new__
        Ch = Change
        FA = _FastAck
        try:
            while row < n:
                c = mk(Ch)
                # dictionary UTF-8 was validated at decode; this decode
                # cannot fail structurally
                c.key = bbuf[ko[row] : ko[row] + kl[row]].decode("utf-8")
                c.change = int(cg[row])
                c.from_ = int(fr[row])
                c.to = int(tv[row])
                c.value = (bbuf[vo[row] : vo[row] + vl[row]]
                           if vl[row] >= 0 else b"")
                c.subset = (bbuf[so[row] : so[row] + sl[row]].decode("utf-8")
                            if sl[row] >= 0 else "")
                # delivery consumes the row BEFORE the handler can raise
                # (the bulk-loop doctrine): a caught raise-then-resume
                # re-enters at the next row, never re-delivering
                row += 1
                self.changes += 1
                self._batch_rows_seen += 1
                ack = mka(FA)
                ack.dec = self
                ack.state = 0
                on_change(c, ack)
                if ack.state != 1:
                    with lock:
                        if ack.state == 0:
                            ack.state = 2  # armed: handler went async
                            self._pending += 1
                if self.destroyed or self._pending > 0 \
                        or self._paused_readers > 0:
                    return
        finally:
            pb["row"] = row
            if _OBS.on and row > row0:
                _M_DEC_CHANGES.inc(row - row0)
            if row >= n and self._pbatch is pb:
                self._pbatch = None
                self._batch_frames_done += 1

    # -- reconcile frames ----------------------------------------------------

    def _reconcile_data(self, chunk: memoryview) -> memoryview | None:
        return self._sized_payload_data(chunk, self._finish_reconcile)

    def _finish_reconcile(self, payload) -> None:
        """Decode one complete reconcile payload and dispatch it whole.

        Structural corruption (bad subtype/version, truncated symbol
        run, trailing bytes) destroys the session with a ProtocolError
        exactly like a corrupt Change payload — the fault-injection
        contract: a reconcile session fails STRUCTURED, never decodes a
        wrong diff from a torn frame."""
        from ..wire import reconcile_codec

        try:
            msg = reconcile_codec.decode_reconcile(payload)
        except ValueError as e:
            self.destroy(self._protocol_error(str(e), cause=e))
            return
        if _OBS.on:
            _M_DEC_RC_FRAMES.inc()
            if _OBS.frames:
                _trace_instant("decoder.frame", offset=self._frame_start,
                               kind="reconcile",
                               wire_len=_header_len(len(payload))
                               + len(payload))
            self._lit_cost_reconcile(len(payload))
        self._state = TYPE_HEADER
        # delivery consumes the frame BEFORE the handler can raise (the
        # change/blob doctrine): a caught raise-then-resume re-enters at
        # the next frame, never re-delivering this message
        self.reconcile_frames += 1
        if self._on_reconcile is not None:
            ack = _FastAck(self)
            self._on_reconcile(msg, ack)
            if ack.state != 1:
                with self._ack_lock:
                    if ack.state == 0:
                        ack.state = 2  # armed: handler went async
                        self._pending += 1
        # default: drop (the unhandled-changes doctrine)

    # -- snapshot frames -----------------------------------------------------

    def _snapshot_data(self, chunk: memoryview) -> memoryview | None:
        return self._sized_payload_data(chunk, self._finish_snapshot)

    def _finish_snapshot(self, payload) -> None:
        """Decode one complete snapshot payload and dispatch it whole.

        Structural corruption (bad subtype/version, truncated chunk
        entry, trailing bytes) destroys the session with a
        ProtocolError exactly like a corrupt Change payload — the
        fault-injection contract: a snapshot session fails STRUCTURED,
        never assembles from a torn frame (a flipped chunk BODY is the
        per-chunk digest verification's job in the joiner)."""
        from ..wire import snapshot_codec

        try:
            msg = snapshot_codec.decode_snapshot(payload)
        except ValueError as e:
            self.destroy(self._protocol_error(str(e), cause=e))
            return
        if _OBS.on:
            _M_DEC_SN_FRAMES.inc()
            if _OBS.frames:
                _trace_instant("decoder.frame", offset=self._frame_start,
                               kind="snapshot",
                               wire_len=_header_len(len(payload))
                               + len(payload))
            self._lit_cost_snapshot(len(payload))
        self._state = TYPE_HEADER
        # delivery consumes the frame BEFORE the handler can raise (the
        # change/blob doctrine): a caught raise-then-resume re-enters at
        # the next frame, never re-delivering this message
        self.snapshot_frames += 1
        if self._on_snapshot is not None:
            ack = _FastAck(self)
            self._on_snapshot(msg, ack)
            if ack.state != 1:
                with self._ack_lock:
                    if ack.state == 0:
                        ack.state = 2  # armed: handler went async
                        self._pending += 1
        # default: drop (the unhandled-changes doctrine)

    # -- blob frames ---------------------------------------------------------

    def _open_blob_if_ready(self) -> None:
        """Create the reader and invoke the app handler.

        The blob-level ``done`` does NOT gate parsing of the blob's own
        payload — the reference hands the handler ``_down`` without a matching
        ``_up`` and instead increments pending at blob END
        (reference: decode.js:171-177,182), so frames *after* the blob wait
        for the app's ack. The latch below reproduces exactly that pairing.
        (The reference defers reader creation to the first payload byte,
        decode.js:180-184; creating at header time additionally supports
        zero-length blobs.)"""
        blob = BlobReader(self, self._missing)
        self._current_blob = blob
        self.blobs += 1
        if _OBS.on:
            _M_DEC_BLOBS.inc()
            if _OBS.frames:
                _trace_instant("decoder.frame", offset=self._frame_start,
                               kind="blob",
                               wire_len=_header_len(self._missing)
                               + self._missing)
            self._lit_cost_blob(self._missing)
        latch = {"ended": False, "acked": False}
        blob._pending_latch = latch

        def done() -> None:
            if latch["acked"]:
                return
            latch["acked"] = True
            if latch["ended"]:
                self._pending -= 1
                self._resume()

        handler = self._on_blob
        if handler is None:
            handler = _drain_blob
            blob._unread = True
        try:
            handler(blob, done)
        finally:
            # a zero-length blob has no payload bytes to route through
            # _blob_data's exception-safe end: if the handler raises,
            # the blob must still END here or _state stays TYPE_BLOB
            # with the reader dangling — a caught raise-then-resume
            # would then fail end() with a spurious mid-frame error
            # (both dispatch paths share this site)
            if self._missing == 0:
                self._end_blob()

    def _blob_data(self, chunk: memoryview) -> memoryview | None:
        blob = self._current_blob
        assert blob is not None
        take = min(len(chunk), self._missing)
        self._parsed += take
        self._missing -= take
        rest = chunk[take:]
        if _OBS.on:
            _M_DEC_BLOB_BYTES.inc(take)
        try:
            if blob._unread:
                # no blob handler registered: no reader for a bytes
                # object, so none is made — a _note_blob_bytes
                # subscriber (digest buffering) gets a read-only view of
                # the caller's memory, which write() may keep (API.md)
                self._note_blob_bytes(chunk[:take].toreadonly())
                blob.received += take
            else:
                # materialize ONCE; bytes are immutable, so the
                # BlobReader and any _note_blob_bytes subscriber share
                # this object instead of re-copying the scratch
                # memoryview
                data = bytes(chunk[:take])
                if _OBS.on:
                    _M_DEC_BLOB_COPIED.inc(take)
                self._note_blob_bytes(data)
                blob._deliver(data)
        except BaseException:
            self._requeue_tail(rest)  # reader raise: keep the tail
            raise
        finally:
            # delivery consumed these bytes even if a reader callback
            # raised: the blob must still END, or _state stays TYPE_BLOB
            # with _current_blob dangling — a caught raise-then-resume
            # on the final chunk would then fail end() with a spurious
            # mid-frame ProtocolError and never fire on_end
            if self._missing == 0:
                self._end_blob()
        return rest

    def _note_blob_bytes(self, data) -> None:
        """Hook: called with each blob payload piece, in order — the
        ``bytes`` object delivered to the BlobReader where a blob
        handler is registered, else a read-only view of the written
        memory.  Base: no-op."""

    def _end_blob(self) -> None:
        blob, self._current_blob = self._current_blob, None
        self._state = TYPE_HEADER
        if blob is not None:
            # Hold the pipeline until the app acks the blob — the
            # `_pending++` of the reference's _onblobend (decode.js:171-177).
            latch = blob._pending_latch
            if not latch["acked"]:
                latch["ended"] = True
                self._pending += 1
            blob._finish()
