"""Kernel-bypass wire pump: batched-syscall transport loops (ISSUE 14).

The r06 capture located the host e2e floor in the Python wire path, not
the crypto: native hashing runs GiB/s while the pump loops in
:mod:`.transport` pay one interpreter round-trip per 64 KiB chunk —
``read_bytes`` call, ``decoder.write``, wake bookkeeping — and hold the
GIL for all of it.  Following the SmartNIC replication shape (PAPERS.md:
move the replication data plane below the host CPU), this module routes
the byte loops through the C extension instead:

* **Receive** (:func:`recv_pump`): one ``dat_pump_recv_scan`` call per
  slab — a blocking wakeup ``read``, a ``MSG_DONTWAIT`` ``recvmmsg``
  drain of whatever the kernel already buffered, and the native frame
  scan, all with the GIL released — then ONE
  :meth:`~.decoder.Decoder.write_indexed` hands the decoder the bytes
  plus the finished frame index.  Python sees only coalesced units:
  columnar ChangeBatch runs, blob extents as memoryviews, control
  frames individually (exactly what the decoder's bulk dispatch already
  surfaces).  On a connection whose receives come back full, a helper
  thread receives the next slabs while the session thread feeds this
  one (``READAHEAD``).
* **Send** (:func:`send_pump`): megabyte pulls from the encoder pushed
  through ``dat_pump_send``'s gather loop (sendmmsg batches, writev
  fallback, partial acceptance resumed natively).
* **Fan-out gather** (:func:`send_spans_nb`): the broadcast hot path —
  BroadcastLog segment memoryviews go to the kernel as (address,
  length) spans through one non-blocking sendmmsg/writev batch per
  dispatcher turn.  Zero Python-owned payload bytes; the dispatcher
  keeps every window/ack/shed decision (ROBUSTNESS.md: the overload
  contract is unchanged, only the byte mover is).

**Route selection** (the ``DAT_CDC_ROUTE`` pattern): ``DAT_PUMP=python``
pins the portable reference pumps in :mod:`.transport`;
``DAT_PUMP=native`` (and the default, when the native library is
available) takes the batched loops.  Unrecognized values resolve to the
default.  Both routes are byte-identical — deliveries, digests,
checkpoints, and structured errors — enforced by the chaos parity
sweep (tests/test_pump_parity.py); the Python pump stays the portable
reference, never a second protocol.

Backpressure is the transport module's contract: the receive pump
starts no receive while the decoder stalls (the kernel socket buffer
absorbs the window) — past a stall it has taken at most ``READAHEAD``
slabs more than the inline pump would — and the send pump stops
pulling while the transport blocks.  PERF.md "Wire pump" has the syscall cost
model and the batch-size sweep.
"""

from __future__ import annotations

import errno
import itertools
import os
import queue
import socket
import stat
import threading
from time import perf_counter as _perf, thread_time as _thread_time
from typing import Callable, Optional

import numpy as np

from ..obs.metrics import OBS as _OBS, counter as _counter, \
    cpu_clock_visit as _cpu_clock_visit, histogram as _histogram
from ..obs import wirecost as _wirecost
from ..runtime import native
from ..utils.trace import span
from .decoder import Decoder, DecoderDestroyedError
from .encoder import Encoder, EncoderDestroyedError
from .transport import WAKE_FALLBACK, recv_over, send_over, \
    write_all as _write_all

__all__ = [
    "effective_pump_route", "recv_pump", "send_pump", "pump_reader",
    "pump_writer", "io_for_socket", "send_spans_nb", "probe_caps",
    "EdgePump", "RecvFan", "recv_step", "recv_fetch", "recv_feed",
    "send_step",
]

# receive slab geometry: cap bounds one pump call's batch (and the
# decoder's largest single bulk index); slice is the per-message recv
# size inside the batch.  Measured on the dev box (PERF.md sweep):
# 2 MiB / 1 MiB is ~1.3x the Python pump on the digest-session shape;
# smaller slices re-enter the interpreter per ~kernel-buffer-full.
PUMP_BUF = 2 << 20
PUMP_SLICE = 1 << 20
# recv_pump's read-ahead: slabs its helper may have received that the
# session thread has not fed yet.  Past a stalled decoder the socket
# stops at most READAHEAD x PUMP_BUF later than an inline pump's would
# (ROBUSTNESS.md).  2, not 1: with a second job queued the helper hands
# slab n+1 back and starts n+2 in ONE hold of the interpreter lock; at
# depth 1 its next receive waits for the session thread to let the
# lock go (PERF.md §6, PR 39)
READAHEAD = 2
# send pull size: one encoder.read per native gather call
PUMP_SEND_CHUNK = 1 << 20

# transport.pump.* telemetry (OBSERVABILITY.md catalog), hoisted at
# import so the disabled path is one attribute load
_M_BATCHES = _counter("transport.pump.batches")
_M_MSGS = _counter("transport.pump.msgs")
_M_SYSCALLS = _counter("transport.pump.syscalls")
_M_SAVED = _counter("transport.pump.syscalls_saved")
_M_BYTES = _counter("transport.pump.bytes")
_M_GATHER_BYTES = _counter("transport.pump.gather.bytes")
_M_FALLBACK = _counter("transport.pump.route.python")
# time spent inside one native pump call, receive or send — the GIL is
# released inside the call and TAKEN BACK before it returns, so beside
# another busy thread (the hub dispatcher, the edge loop's feeds) a
# reading ends when the caller has the lock again: released time plus
# the wait for the lock, not released time alone
_H_NATIVE = _histogram("transport.pump.native.seconds")
# the two clocks of ONE region, recv_fetch's native receive, on
# whichever thread ran it (a RecvFan helper, or the edge loop inline):
# wall seconds and that thread's CPU seconds — the kernel's socket
# copies and the frame scan — the wall clock on every lit receive, the
# CPU clock on one in CPU_CLOCK_EVERY.  Mean wall minus mean cpu is the
# caller's wait to have the interpreter lock back after the receive
_H_FETCH = _histogram("pump.fetch.seconds")
_H_FETCH_CPU = _histogram("pump.fetch.cpu_seconds")
_fetches = itertools.count()  # lit receives; next() is atomic
# recv_pump's read-ahead, lit: slabs received on its helper, and of
# them the ones already in when the session thread asked (the hit share)
_M_RA_SLABS = _counter("pump.readahead.slabs")
_M_RA_READY = _counter("pump.readahead.ready")


def effective_pump_route() -> str:
    """The ONE owner of pump-route resolution (the
    ``DAT_CDC_ROUTE``/``effective_route`` pattern): consult ``DAT_PUMP``
    (``native`` / ``python``), defaulting to ``native`` when the C
    engine is loadable; unrecognized values resolve to the default, and
    ``native`` silently degrades to ``python`` on toolchain-less hosts
    — the route that runs is always a route that exists."""
    route = os.environ.get("DAT_PUMP")
    if route == "python":
        return "python"
    return "native" if native.available() else "python"


def probe_caps() -> dict:
    """Snapshot of the pump's runtime probe — what ``--stats-fd``
    records carry so an operator can see which syscall tier a host
    actually serves (the probe never gates the pump: each call
    degrades per-fd)."""
    caps = native.pump_probe()
    return {
        "route": effective_pump_route(),
        "native_available": caps is not None,
        "recvmmsg": bool(caps & 1) if caps is not None else False,
        "sendmmsg": bool(caps & 2) if caps is not None else False,
    }


class _RecvState:
    """Per-pump-loop native index buffers, allocated once per session.

    The receive SLAB is not here: each batch lands in a fresh
    allocation handed to the decoder as a zero-copy view (the decoder
    may pin slices in its overflow/bulk cursors arbitrarily long, and
    re-reading into a shared buffer under them would corrupt the wire
    — while copying out of it, the alternative, costs a second pass
    over every byte)."""

    __slots__ = ("cap", "starts", "lens", "ids", "stats")

    def __init__(self, cap: int):
        self.cap = cap
        # index capacity is sized for the TYPICAL frame density, not
        # the 2-byte worst case (that would be ~17 bytes of index per
        # 2 wire bytes, per session): a denser slab comes back as a
        # valid partial index and its tail re-enters the decoder's
        # overflow — correctness never depends on icap
        icap = cap // 16 + 1
        self.starts = np.empty(icap, dtype=np.int64)
        self.lens = np.empty(icap, dtype=np.int64)
        self.ids = np.empty(icap, dtype=np.uint8)
        self.stats = np.zeros(2, dtype=np.int64)


def _lit_rx(decoder, nbytes: int) -> None:
    """Lit-side transport ground truth, receive direction (ISSUE 20):
    the pump IS the transport, so raw received bytes anchor the wire
    cost ledger's tiling audit.  Callers hold the ``_OBS.on`` gate —
    the hot loops stay bytecode-free of this module's plane."""
    _wirecost.note_transport(
        getattr(decoder, "cost_link", "session"), "rx", nbytes)


def _lit_tx(encoder, nbytes: int) -> None:
    """Lit-side transport ground truth, send direction (ISSUE 20)."""
    _wirecost.note_transport(
        getattr(encoder, "cost_link", "session"), "tx", nbytes)


def _metered_reader(decoder, read_bytes):
    """Wrap a python-route ``read_bytes`` so the fallback pump reports
    the same transport ground truth the native loop does (per-read
    ``_OBS.on`` fork: the dark path adds one attribute load)."""
    def metered(n: int) -> bytes:
        data = read_bytes(n)
        if data and _OBS.on:
            _lit_rx(decoder, len(data))
        return data

    return metered


def _note_batch(nbytes: int, stats) -> None:
    syscalls = int(stats[0])
    msgs = int(stats[1])
    _M_BATCHES.inc()
    _M_MSGS.inc(msgs)
    _M_SYSCALLS.inc(syscalls)
    if msgs > syscalls:
        _M_SAVED.inc(msgs - syscalls)
    _M_BYTES.inc(nbytes)


def recv_pump(decoder: Decoder, fd: int,
              tap: Optional[Callable[[bytes], None]] = None,
              cap: int = PUMP_BUF) -> None:
    """Pump ``fd`` into ``decoder`` until EOF or destroy, batched.

    The native twin of :func:`.transport.recv_over` (same flow-control
    contract: no receive starts while the decoder stalls, resuming on
    its drain watcher).  ``tap`` observes every received slab as the
    exact ``bytes`` object the decoder is fed — the fan-out source's
    publish hook, byte-identical to wrapping ``read_bytes``.  Falls
    back to the Python pump when the route (or the library) says so.

    Each slab is :func:`recv_fetch` then :func:`recv_feed`, the edge
    legs' two halves.  A connection starts with both on this thread,
    in a row; once a receive comes back with a full ``PUMP_SLICE``
    (``EdgePump.bulk``) the receives move to one helper thread that
    runs up to ``READAHEAD`` slabs ahead of the feeds
    (:class:`_ReadAhead`).  The feeds — EOF and errors, the tap, the
    lit counters, ``decoder.write_indexed`` — stay here, in slab
    order, and ``decoder.end()`` follows the last of them.
    """
    if effective_pump_route() != "native":
        if _OBS.on:
            _M_FALLBACK.inc()
        read_bytes = _tapped_reader(fd, tap)
        recv_over(decoder, _metered_reader(decoder, read_bytes))
        return
    wake = threading.Event()
    decoder._add_drain_watcher(wake.set)
    ahead = _ReadAhead(fd, cap, wake)
    try:
        while not decoder.destroyed:
            pump, fetched = ahead.next(decoder)
            if pump is None:  # destroyed while it waited for a receive
                return
            if isinstance(fetched, BaseException):
                raise fetched  # the helper's, in slab order
            r = fetched[1]
            if r is None:  # library vanished mid-session (tests reset)
                # a receive still in flight finds no library either and
                # returns without reading: the fd's bytes are all ahead
                ahead.close(shut=False)
                recv_over(decoder,
                          _metered_reader(decoder, _tapped_reader(fd, tap)))
                return
            wake.clear()
            if r[0] > 0:
                # frame callbacks, blob join, digest submits (a private
                # pipeline's digest.* stages nest inside)
                with span("decode.write", bytes=r[0], frames=r[1]):
                    nbytes, eof = recv_feed(pump, decoder, fetched, tap)
            else:  # EOF, or an errno recv_feed raises
                nbytes, eof = recv_feed(pump, decoder, fetched, tap)
            if eof:
                if not decoder.destroyed and not decoder.finished:
                    decoder.end()
                return
            if not nbytes:  # EAGAIN: a receive timeout on this blocking leg
                raise OSError(errno.EAGAIN, os.strerror(errno.EAGAIN))
            while not (decoder.writable() or decoder.destroyed
                       or decoder.finished):
                wake.wait(WAKE_FALLBACK)
                wake.clear()
            ahead.release(pump)
    finally:
        ahead.close()
        decoder._remove_drain_watcher(wake.set)


def _recv_spanned(pump: "EdgePump") -> tuple:
    # recv_pump's receive, on whichever thread makes it: the stage
    # pump_busy reads.  A blocking read plus the frame scan: where the
    # peer keeps the socket full it is the kernel's copy and the scan,
    # where it does not it is the wait for the peer — the one wait a
    # stage span may bracket (OBSERVABILITY.md)
    with span("pump.recv"):
        return recv_fetch(pump)


class _ReadAhead:
    """:func:`recv_pump`'s receives.  Inline until the connection shows
    bulk, then on one helper thread (a :class:`RecvFan` of one) that
    keeps ``READAHEAD`` receives going while the session thread feeds.

    * Depth: a receive starts only from :meth:`next`, which the session
      thread calls once the decoder is writable — so a stalled decoder
      has at most ``READAHEAD`` slabs received past it.
    * One :class:`EdgePump` a slab in flight: its ``_RecvState`` holds
      that slab's frame index, and ``Decoder._install_index`` keeps
      views of it while the decoder is parked on the slab's bulk
      cursor; :meth:`release` takes a pump back only once the decoder
      has drained past its slab.
    * Teardown: :meth:`close` wakes a helper blocked in a read with
      ``shutdown(SHUT_RD)`` — never a close: the caller owns the fd,
      and a number closed under a blocked reader can be handed to
      another connection before the read returns — then joins it under
      ``RecvFan.close``'s bound.  Only a socket engages: a pipe has no
      read half to shut."""

    __slots__ = ("fd", "cap", "wake", "free", "last", "fan", "inflight")

    def __init__(self, fd: int, cap: int, wake: threading.Event):
        self.fd = fd
        self.cap = cap
        self.wake = wake
        self.free: list = []  # pumps whose slab the decoder is past
        self.last: Optional[EdgePump] = None  # the pump fed last
        self.fan: Optional[RecvFan] = None
        self.inflight = 0

    def _pump(self) -> EdgePump:
        return self.free.pop() if self.free else EdgePump(self.fd, self.cap)

    def _start(self) -> None:
        pump = self._pump()
        self.fan.start(pump, pump)
        self.inflight += 1

    def next(self, decoder: Decoder) -> tuple:
        """``(pump, fetched)`` of the next slab in order —
        :func:`recv_feed`'s arguments, or what the helper raised —
        and ``(None, None)`` if the decoder is destroyed meanwhile."""
        if self.fan is None:
            last = self.last
            if last is None or not last.bulk or not _is_socket(self.fd):
                pump = self._pump()
                return pump, _recv_spanned(pump)
            self.fan = RecvFan(1, name="pump-rx", fetch=_recv_spanned,
                               on_done=self.wake.set)
        if not self.inflight:
            self._start()
        got = self.fan.poll()
        lit = _OBS.on
        if got is None:
            # the session thread's wait for its helper's receive
            with span("pump.wait"):
                while got is None:
                    if decoder.destroyed:
                        return None, None
                    self.wake.wait(WAKE_FALLBACK)
                    self.wake.clear()
                    got = self.fan.poll()
        elif lit:
            _M_RA_READY.inc()
        if lit:
            _M_RA_SLABS.inc()
        self.inflight -= 1
        pump, fetched = got
        if (not isinstance(fetched, BaseException) and fetched[1] is not None
                and fetched[1][0] > 0):
            while self.inflight < READAHEAD:
                self._start()
        return pump, fetched

    def release(self, pump: EdgePump) -> None:
        """The decoder is writable again after ``pump``'s slab: nothing
        it holds reads that slab's index any more."""
        self.last = pump
        self.free.append(pump)

    def close(self, shut: bool = True) -> None:
        fan, self.fan = self.fan, None
        if fan is None:
            return
        if self.inflight and shut:
            _shut_rd(self.fd)
        fan.close()


def _is_socket(fd: int) -> bool:
    try:
        return stat.S_ISSOCK(os.fstat(fd).st_mode)
    except OSError:
        return False


def _shut_rd(fd: int) -> None:
    """Shut ``fd``'s read half: a read blocked on it returns."""
    sock = socket.socket(fileno=fd)
    try:
        sock.shutdown(socket.SHUT_RD)
    except OSError:
        pass  # the peer or the caller got there first
    finally:
        sock.detach()


def _tapped_reader(fd: int, tap) -> Callable[[int], bytes]:
    if tap is None:
        return lambda n: os.read(fd, n)

    def read_bytes(n: int) -> bytes:
        data = os.read(fd, n)
        if data:
            tap(data)
        return data

    return read_bytes


def send_pump(encoder: Encoder, fd: int,
              close: Optional[Callable[[], None]] = None,
              on_progress: Optional[Callable[[], None]] = None) -> None:
    """Pump ``encoder`` to ``fd`` until EOF or destroy, batched.

    The native twin of :func:`.transport.send_over`: megabyte pulls,
    each pushed through one GIL-released native gather call that owns
    the partial-write resume loop.  ``on_progress`` fires after every
    accepted batch (the sidecar's reply-stall clock).  Falls back to
    the Python pump when the route (or the library) says so."""
    if effective_pump_route() != "native":
        if _OBS.on:
            _M_FALLBACK.inc()

        def write_bytes(data) -> None:
            _write_all(fd, data)
            if _OBS.on and len(data):
                _lit_tx(encoder, len(data))
            if on_progress is not None:
                on_progress()

        send_over(encoder, write_bytes, close=close)
        return
    addrs = np.zeros(1, dtype=np.int64)
    lens = np.zeros(1, dtype=np.int64)
    stats = np.zeros(2, dtype=np.int64)
    readable = threading.Event()
    encoder._attach_readable(readable.set)
    # wake hook only: sets an Event, never blocks (ISSUE 17 satellite)
    # datlint: allow-callback-escape
    encoder.on_error(lambda _e: readable.set())
    try:
        while True:
            try:
                data = encoder.read(PUMP_SEND_CHUNK)
            except EncoderDestroyedError:
                break
            if data is None:  # finalized and drained
                break
            if not data:
                readable.wait(WAKE_FALLBACK)
                readable.clear()
                continue
            arr = np.frombuffer(data, dtype=np.uint8)
            addrs[0] = arr.__array_interface__["data"][0]
            lens[0] = len(data)
            t0 = _perf()
            # `data`/`arr` stay referenced (bytes pinned) for the call
            w = native.pump_send_spans(fd, addrs, lens, 1, stats)
            if _OBS.on:
                _H_NATIVE.observe(_perf() - t0)
            if w is None:  # library vanished mid-session: finish plain
                _write_all(fd, data)
                w = len(data)
            elif w < 0:
                raise OSError(-w, os.strerror(-w))
            if _OBS.on:
                _note_batch(int(w), stats)
                _lit_tx(encoder, int(w))
            if on_progress is not None:
                # the sidecar's reply-stall clock: one monotonic read
                # datlint: allow-callback-escape
                on_progress()
    finally:
        encoder._detach_readable()
        if close is not None:
            try:
                # a shutdown/close syscall on the way out — bounded
                # datlint: allow-callback-escape
                close()
            except OSError:
                pass


def pump_reader(fd: int, cap: int = PUMP_BUF) -> Callable[[int], bytes]:
    """A ``read_bytes`` drop-in serving batched native receives — the
    pump selector for callers that feed decoders through callables
    (the reconcile/snapshot drivers' ``recv_over`` surface).  May
    return MORE than the requested hint (every call site feeds a
    decoder, which takes any chunking); EOF is ``b""``, transport
    errors raise ``OSError`` — the ``os.read`` contract."""
    if effective_pump_route() != "native":
        return lambda n: os.read(fd, n)
    # reusable slab: unlike recv_pump's zero-copy handoff, this surface
    # returns an owned bytes per call (the os.read contract), so the
    # buffer can be recycled.  The index arrays are 1-element on
    # purpose: this caller feeds a decoder through write() (the index
    # would be thrown away), and a full index array would make the
    # native call frame-scan every slab for nothing — capacity overflow
    # stops the scan after one frame
    buf = np.empty(cap, dtype=np.uint8)
    starts = np.zeros(1, dtype=np.int64)
    lens = np.zeros(1, dtype=np.int64)
    ids = np.zeros(1, dtype=np.uint8)
    stats = np.zeros(2, dtype=np.int64)

    def read_bytes(_hint: int) -> bytes:
        t0 = _perf()
        r = native.pump_recv_scan(fd, buf, PUMP_SLICE, starts,
                                  lens, ids, stats)
        if r is None:
            return os.read(fd, _hint)
        nbytes = r[0]
        if _OBS.on:
            _H_NATIVE.observe(_perf() - t0)
        if nbytes < 0:
            raise OSError(-nbytes, os.strerror(-nbytes))
        if nbytes == 0:
            return b""
        if _OBS.on:
            _note_batch(nbytes, stats)
        return buf[:nbytes].tobytes()

    return read_bytes


def pump_writer(fd: int) -> Callable[[bytes], None]:
    """A ``write_bytes`` drop-in pushing through the native gather loop
    (blocking; partial writes resumed natively) — the send-side twin of
    :func:`pump_reader`."""
    if effective_pump_route() != "native":
        return lambda data: _write_all(fd, data)
    addrs = np.zeros(1, dtype=np.int64)
    lens = np.zeros(1, dtype=np.int64)
    stats = np.zeros(2, dtype=np.int64)

    def write_bytes(data) -> None:
        if not len(data):
            return
        arr = np.frombuffer(data, dtype=np.uint8)
        addrs[0] = arr.__array_interface__["data"][0]
        lens[0] = len(arr)
        t0 = _perf()
        w = native.pump_send_spans(fd, addrs, lens, 1, stats)
        if w is None:
            _write_all(fd, data)
            return
        if _OBS.on:
            _H_NATIVE.observe(_perf() - t0)
        if w < 0:
            raise OSError(-w, os.strerror(-w))
        if _OBS.on:
            _note_batch(int(w), stats)

    return write_bytes


def io_for_socket(conn) -> tuple:
    """``(read_bytes, write_bytes)`` for a connected socket through the
    pump selector: the batched native reader/writer when routed (the
    reconcile/snapshot drivers' transports upgrade with zero new
    flags), the plain socket calls otherwise."""
    if effective_pump_route() != "native":
        return conn.recv, conn.sendall
    return pump_reader(conn.fileno()), pump_writer(conn.fileno())


class SpanGather:
    """Reusable (address, length) span arrays for the fan-out gather
    path: one instance per dispatcher, refilled per serve turn —
    payload bytes never become Python objects, only their addresses
    do."""

    __slots__ = ("addrs", "lens", "stats", "_arrs")

    def __init__(self, cap: int = 1024):
        self.addrs = np.zeros(cap, dtype=np.int64)
        self.lens = np.zeros(cap, dtype=np.int64)
        self.stats = np.zeros(2, dtype=np.int64)
        self._arrs: list = []  # keeps span buffers pinned across a call

    def fill(self, views) -> int:
        """Load ``views`` (memoryviews/bytes) as spans; returns the
        count.  The numpy wraps are zero-copy — addresses point into
        the callers' buffers, which this object pins until the next
        :meth:`fill`."""
        n = len(views)
        if n > len(self.addrs):
            self.addrs = np.zeros(n, dtype=np.int64)
            self.lens = np.zeros(n, dtype=np.int64)
        arrs = []
        for i, v in enumerate(views):
            a = np.frombuffer(v, dtype=np.uint8)
            arrs.append(a)
            self.addrs[i] = a.__array_interface__["data"][0]
            self.lens[i] = len(a)
        self._arrs = arrs
        return n

    def release(self) -> None:
        self._arrs = []


class EdgePump:
    """Per-session pump state for the event-driven edge (ISSUE 17):
    the batched-syscall primitives of this module, re-cut as ONE
    bounded non-blocking turn per call instead of a thread-owned loop.

    On the edge ``fd`` MUST be non-blocking — the edge loop sets
    ``O_NONBLOCK`` at admission and never clears it; every kernel call
    below is bounded by that flag (would-block returns immediately),
    which is what lets :meth:`EdgeLoop._dispatch_loop` inline these
    sites and still certify ``bounded-blocking``.  :func:`recv_pump`
    holds one over its blocking fd for each slab in flight and uses
    the two receive halves alone.  The native route degrades
    per-call to plain ``os.read``/``os.write`` exactly like the
    thread pumps (the route that runs is always a route that
    exists)."""

    __slots__ = ("fd", "cap", "recv_st", "pending", "gather", "native",
                 "bulk")

    def __init__(self, fd: int, cap: int = PUMP_BUF):
        self.fd = fd
        self.cap = cap
        self.native = effective_pump_route() == "native"
        self.recv_st = _RecvState(cap) if self.native else None
        self.gather = SpanGather(cap=1) if self.native else None
        self.pending: Optional[memoryview] = None  # unsent reply tail
        # what the last native receive observed of the socket: it came
        # back with a full PUMP_SLICE or more, so the peer keeps the
        # kernel's buffer full — the edge loop's cue to run this
        # session's next receive beside its neighbours' (recv_fetch on
        # a helper thread) instead of in a row
        self.bulk = False


def recv_step(pump: EdgePump, decoder: Decoder, tap=None) -> tuple:
    """ONE bounded receive turn: drain what the kernel already
    buffered on ``pump.fd`` into ``decoder``, never waiting.  Returns
    ``(nbytes, eof)``; ``(0, False)`` means would-block (wait for the
    selector's next READ event).  Native route: :func:`recv_fetch`
    then :func:`recv_feed`, in a row; Python route: ``os.read`` until
    ``EAGAIN``, EOF, decoder stall, or the ``PUMP_BUF`` turn budget —
    a faulted neighbor can cost this session at most one slab of
    latency per turn."""
    if pump.native:
        return recv_feed(pump, decoder, recv_fetch(pump), tap)
    res = _recv_step_py(pump, decoder, tap)
    if _OBS.on and res[0]:
        _lit_rx(decoder, res[0])
    return res


def recv_fetch(pump: EdgePump) -> tuple:
    """The RECEIVE half of the native :func:`recv_step`: a fresh slab
    and one ``dat_pump_recv_scan`` batch into it (its first ``read``
    returns ``-EAGAIN`` on the non-blocking fd instead of sleeping),
    the interpreter lock released for the whole call.  Touches the
    descriptor, the slab and the session's own :class:`_RecvState` and
    nothing else, so the edge loop may run it on a helper thread
    (one receive in flight a session, fed before the next begins).
    Lit, the call's two clocks feed ``pump.fetch.seconds`` /
    ``pump.fetch.cpu_seconds`` from the thread that made it.
    Returns what :func:`recv_feed` takes: ``(slab, result, seconds)``."""
    st = pump.recv_st
    buf = np.empty(st.cap, dtype=np.uint8)  # fresh: see _RecvState
    # one gate check a slab; dark, no CPU clock is read — and lit, on
    # one receive in CPU_CLOCK_EVERY, inside the wall clock's reads
    lit = _OBS.on
    clocked = lit and _cpu_clock_visit(next(_fetches))
    t0 = _perf()
    if clocked:
        c0 = _thread_time()
    r = native.pump_recv_scan(pump.fd, buf, PUMP_SLICE, st.starts,
                              st.lens, st.ids, st.stats)
    if clocked:
        cpu = _thread_time() - c0
    seconds = _perf() - t0
    if lit:
        _H_FETCH.observe(seconds)
        if clocked:
            _H_FETCH_CPU.observe(cpu)
    return buf, r, seconds


def recv_feed(pump: EdgePump, decoder: Decoder, fetched: tuple,
              tap=None) -> tuple:
    """The FEED half of the native :func:`recv_step`, on the thread
    that owns the decoder: a transport error raises here, EOF and
    would-block are told apart here, then the tap, the lit counters
    and ``decoder.write_indexed``.  Returns ``(nbytes, eof)``."""
    buf, r, seconds = fetched
    if r is None:  # library vanished mid-session (tests reset)
        pump.native = False
        pump.bulk = False
        return recv_step(pump, decoder, tap)
    st = pump.recv_st
    nbytes, nframes, consumed, _err = r
    pump.bulk = nbytes >= PUMP_SLICE
    if _OBS.on:
        _H_NATIVE.observe(seconds)
    if nbytes in (-11, -4):  # EAGAIN / EINTR: retry next turn
        return (0, False)
    if nbytes < 0:
        raise OSError(-nbytes, os.strerror(-nbytes))
    if nbytes == 0:
        return (0, True)
    if _OBS.on:
        _note_batch(nbytes, st.stats)
        _lit_rx(decoder, nbytes)
    data = memoryview(buf[:nbytes])  # as recv_pump: the prefix's own
    if tap is not None:
        # the broadcast tee (FanoutServer.publish): an append +
        # O(1) mark under the server lock — never blocks the loop
        # datlint: allow-callback-escape
        tap(data)
    try:
        decoder.write_indexed(data, st.starts, st.lens, st.ids,
                              nframes, consumed)
    except DecoderDestroyedError:
        pass  # the loop's teardown predicate sees dec.destroyed
    return (nbytes, False)


# RecvFan.close's bound on each helper's exit: a helper is at most one
# non-blocking receive away from its sentinel (recv_pump's, one read on
# a socket whose read half _ReadAhead.close has just shut)
_FAN_JOIN_TIMEOUT = 5.0


class RecvFan:
    """A few helper threads that run :func:`recv_fetch` (or ``fetch``)
    for the edge loop, so that one turn's bulk sessions are received
    side by side, and one for :func:`recv_pump`'s read-ahead: the
    kernel's socket copies and the frame scan need no interpreter,
    only a core each.  A helper touches what ``recv_fetch`` touches —
    a descriptor, a slab, the session's ``_RecvState`` — and hands the
    result back untouched; whatever it raised is handed back too and
    raised by the caller.  On the edge every descriptor is
    ``O_NONBLOCK`` (the :class:`EdgePump` contract), so a started
    receive returns without sleeping and :meth:`wait_one` is bounded
    by construction.  ``recv_pump``'s descriptor blocks: it waits
    through :meth:`poll` and its own wake event, which ``on_done``
    (run on the helper after each hand-back) and its decoder's destroy
    both set."""

    __slots__ = ("_jobs", "_done", "_threads", "_fetch", "_on_done")

    def __init__(self, helpers: int, name: str = "edge-rx",
                 fetch: Optional[Callable] = None,
                 on_done: Optional[Callable[[], None]] = None):
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._done: queue.SimpleQueue = queue.SimpleQueue()
        self._fetch = fetch
        self._on_done = on_done
        self._threads = [
            threading.Thread(target=self._run, name=f"{name}-{i}",
                             daemon=True) for i in range(helpers)]
        for t in self._threads:
            t.start()

    def _run(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            token, pump = job
            try:
                fetched = (self._fetch or recv_fetch)(pump)
            except BaseException as e:  # the caller's to raise
                fetched = e
            self._done.put((token, fetched))
            if self._on_done is not None:
                # a wake hook: recv_pump's Event.set, never blocks
                # datlint: allow-callback-escape
                self._on_done()

    def start(self, token, pump: EdgePump) -> None:
        """Begin one receive on ``pump``; ``token`` comes back with it."""
        self._jobs.put((token, pump))

    def wait_one(self) -> tuple:
        """``(token, fetched)`` of the next started receive to complete;
        ``fetched`` is :func:`recv_feed`'s argument, or the exception
        the helper's call raised."""
        return self._done.get()

    def poll(self) -> Optional[tuple]:
        """:meth:`wait_one`'s result if one is in, else ``None``."""
        try:
            return self._done.get(False)
        except queue.Empty:
            return None

    def close(self) -> None:
        """End the helpers once their started receives are through."""
        for _ in self._threads:
            self._jobs.put(None)
        for t in self._threads:
            t.join(_FAN_JOIN_TIMEOUT)


def _recv_step_py(pump: EdgePump, decoder: Decoder, tap=None) -> tuple:
    """The python arm of :func:`recv_step` (one bounded ``os.read``
    turn); split out so the transport ground-truth noting forks ONCE on
    the final byte total instead of at every return point."""
    total = 0
    while total < pump.cap:
        try:
            # bounded: pump.fd is O_NONBLOCK by the EdgePump contract
            # — a stalled peer surfaces as BlockingIOError, never a
            # sleeping read under the loop
            # datlint: allow-blocking-reachable(os-io)
            data = os.read(pump.fd, PUMP_SLICE)
        except BlockingIOError:
            return (total, False)
        except InterruptedError:
            continue
        if not data:
            return (total, True)
        total += len(data)
        if tap is not None:
            # same broadcast tee as the native arm above
            # datlint: allow-callback-escape
            tap(data)
        try:
            ok = decoder.write(data)
        except DecoderDestroyedError:
            return (total, False)
        if not ok:
            return (total, False)  # decoder stall: the loop gates reads
    return (total, False)


# one send turn pushes at most this many pulls — the encoder's
# high-water mark bounds what it can buffer, this bounds the turn even
# against a pathological producer
_SEND_TURN_PULLS = 8


def send_step(pump: EdgePump, encoder: Encoder) -> tuple:
    """ONE bounded send turn: push encoder output to ``pump.fd`` until
    would-block, the encoder runs dry, or the turn budget.  Returns
    ``(accepted, finished, blocked)`` — ``finished`` means the encoder
    is finalized AND fully drained (reply EOF: the loop may shut down
    the write half); ``blocked`` means the kernel refused bytes we
    still hold (watch ``EVENT_WRITE``).  Native route:
    :func:`send_spans_nb` gather batches; Python route: non-blocking
    ``os.write`` with the partial tail stashed in ``pump.pending``."""
    res = _send_step_impl(pump, encoder)
    if _OBS.on and res[0]:
        _lit_tx(encoder, res[0])
    return res


def _send_step_impl(pump: EdgePump, encoder: Encoder) -> tuple:
    """The engine of :func:`send_step`; split out so the transport
    ground-truth noting forks ONCE on the turn's accepted-byte total
    instead of at every return point."""
    accepted = 0
    for _ in range(_SEND_TURN_PULLS):
        if pump.pending is None:
            try:
                data = encoder.read(PUMP_SEND_CHUNK)
            except EncoderDestroyedError:
                return (accepted, True, False)
            if data is None:  # finalized and drained
                return (accepted, True, False)
            if not data:  # nothing ready (producer still appending)
                return (accepted, False, False)
            pump.pending = memoryview(data) if not isinstance(
                data, memoryview) else data
        view = pump.pending
        if pump.native:
            n = pump.gather.fill([view])
            try:
                w = send_spans_nb(pump.fd, pump.gather, n)
            except OSError as e:
                if e.errno == 38:  # ENOSYS: library vanished, degrade
                    pump.native = False
                    continue
                raise
            finally:
                pump.gather.release()
        else:
            try:
                # bounded: pump.fd is O_NONBLOCK by the EdgePump
                # contract — would-block is an exception, not a sleep
                # datlint: allow-blocking-reachable(os-io)
                w = os.write(pump.fd, view)
            except BlockingIOError:
                w = 0
            except InterruptedError:
                w = 0
        accepted += w
        if w < len(view):
            pump.pending = view[w:] if w else view
            return (accepted, False, True)
        pump.pending = None
    return (accepted, False, False)


def send_spans_nb(fd: int, gather: SpanGather, n: int) -> int:
    """Push ``n`` loaded spans to non-blocking ``fd`` through one
    native gather batch (sendmmsg/writev until EAGAIN).  Returns bytes
    accepted (0 = would-block); raises ``OSError`` on a dead transport
    — exactly the ``os.writev`` contract the fan-out dispatcher's
    bookkeeping is written against."""
    t0 = _perf()
    w = native.pump_send_spans(fd, gather.addrs, gather.lens, n,
                               gather.stats, nonblocking=True)
    if w is None:
        raise OSError(38, "native pump unavailable")  # ENOSYS
    if _OBS.on:
        _H_NATIVE.observe(_perf() - t0)
    if w < 0:
        raise OSError(-w, os.strerror(-w))
    if _OBS.on and w:
        _note_batch(w, gather.stats)
        _M_GATHER_BYTES.inc(w)
    return w
