"""The literal sidecar endpoint: a daemon foreign clients pipe wire bytes to.

The reference's deployment shape is a stream piped into a socket
(reference: example.js:53 ``encode.pipe(decode)``, README.md's
``encode.pipe(socket)``): any process that speaks the dat replication
wire format can connect.  This module makes the TPU data plane
reachable the same way — no Python client required:

    python -m dat_replication_protocol_tpu.sidecar --stdio
    python -m dat_replication_protocol_tpu.sidecar --tcp 127.0.0.1:7531

A client pipes a session (changes + blobs) in; the sidecar decodes it
with the ``backend='tpu'`` decoder (content-hashing every change
payload and blob through the device/host digest engine the routing
layer picks) and streams a *reply session* back on the same connection:

* one ``Change`` per digest, in digest-completion order (submit order
  per the pipeline's completion queue);
* ``key``   = ``"change-<seq>"`` or ``"blob-<seq>"`` (<seq> is the
  0-based arrival index of that kind — self-describing, so the reply
  needs no state from the request stream);
* ``subset`` = ``"digest:change"`` / ``"digest:blob"``;
* ``change`` = <seq>, ``from`` = 0, ``to`` = 1;
* ``value`` = the 32-byte BLAKE2b-256 digest.

Flush-before-finalize holds end-to-end: when the client finalizes its
stream, every digest for submitted work is encoded onto the reply
before the reply stream finalizes (TpuDecoder._maybe_finalize flushes
the pipeline first).  A protocol error destroys both directions, so a
malformed client observes EOF rather than a hang.
"""

from __future__ import annotations

import json
import os
import socket
import sys
import threading
import time

from .obs import device as obs_device
from .obs import events as obs_events
from .obs import flight as obs_flight
from .obs import http as obs_http
from .obs import metrics as obs_metrics
from .obs import tracing as obs_tracing
from .obs.events import emit as _emit
from .obs.metrics import OBS as _OBS, counter as _counter
from .obs.tracing import trace_span as _trace_span
from .obs.propagation import PROPAGATION as _PROPAGATION
from .obs.wirecost import WIRECOST as _WIRECOST
from .obs.watermarks import WATERMARKS as _WATERMARKS
from .session import pump as session_pump
from .session.transport import recv_over, send_over
# one owner for the blocking write-all loop (session/transport.py; the
# pump module's Python fallback binds the same function)
from .session.transport import write_all as _write_all

DIGEST_SUBSET_CHANGE = "digest:change"
DIGEST_SUBSET_BLOB = "digest:blob"

# reply-drain defaults: a client that finished sending but never reads
# its reply must not park a session thread forever (ADVICE.md round 5).
DEFAULT_DRAIN_TIMEOUT = 600.0
_DRAIN_POLL = 0.25

DEFAULT_STATS_INTERVAL = 5.0

_M_SESSIONS = _counter("sidecar.sessions")
_M_STALLS = _counter("sidecar.stalls")

# hub mode (ISSUE 8): ONE shared ReplicationHub across every accepted
# connection; snapshot_stats() carries its per-session breakdown so
# --stats-fd lines attribute traffic per peer
_ACTIVE_HUB = None

# fan-out mode (ISSUE 9): ONE shared FanoutServer broadcasting the
# source session's wire to every subscriber connection
_ACTIVE_FANOUT = None

# replica mode (ISSUE 15): the gossip node (or its driver) whose
# round/peer/quarantine counters --stats-fd and /snapshot carry — the
# fleet plane's per-replica convergence input
_ACTIVE_GOSSIP = None

# edge mode (ISSUE 17): the event-driven EdgeLoop whose session-table
# aggregate --stats-fd and /snapshot carry, and whose admission stage
# fronts /healthz (it composes the hub's — edge wins the precedence)
_ACTIVE_EDGE = None


# what main() resolved for digest batches (backend.tpu_backend.
# resolve_digest_engine): engine + the device as jax reports it — one
# stderr line at start-up, and the ``device`` record of every snapshot
_DEVICE_RECORD = None


def set_device_record(rec) -> None:
    """Install the resolved-engine record ``--stats-fd`` snapshots carry
    (None detaches)."""
    global _DEVICE_RECORD
    _DEVICE_RECORD = rec


def set_active_edge(loop) -> None:
    """Install the :class:`~.edge.EdgeLoop` whose session-table
    aggregate ``--stats-fd`` snapshots carry (None detaches)."""
    global _ACTIVE_EDGE
    _ACTIVE_EDGE = loop


def set_active_gossip(driver) -> None:
    """Install the gossip driver/node whose snapshot() record
    ``--stats-fd`` snapshots carry (None detaches)."""
    global _ACTIVE_GOSSIP
    _ACTIVE_GOSSIP = driver


def set_active_hub(hub) -> None:
    """Install the hub whose per-session breakdown ``--stats-fd``
    snapshots carry (None detaches)."""
    global _ACTIVE_HUB
    _ACTIVE_HUB = hub


def set_active_fanout(server) -> None:
    """Install the fan-out server whose per-peer breakdown
    ``--stats-fd`` snapshots carry (None detaches)."""
    global _ACTIVE_FANOUT
    _ACTIVE_FANOUT = server


def run_session(read_bytes, write_bytes, close_write=None,
                drain_timeout: float | None = DEFAULT_DRAIN_TIMEOUT,
                hub=None, session_key: str | None = None,
                rx_fd: int | None = None, tx_fd: int | None = None,
                publish=None) -> dict:
    """Serve one wire session over a blocking byte pair.

    ``read_bytes(n)`` / ``write_bytes(data)`` follow the
    :mod:`..session.transport` contract (block on congestion, ``b''``
    at EOF).  Returns counters for observability:
    ``{"changes": n, "blobs": n, "bytes": n, "digests": n, "ok": bool}``.

    ``rx_fd`` / ``tx_fd`` (ISSUE 14): the raw descriptors behind the
    byte pair, when the caller has them.  With the native pump routed
    (``DAT_PUMP``, :func:`~..session.pump.effective_pump_route`) the
    session's byte loops run through the C extension's batched-syscall
    pumps instead of ``read_bytes``/``write_bytes`` — byte-identical
    deliveries, digests, and errors, an order less interpreter work.
    Callable-only callers (tests, custom transports) get the Python
    pumps unchanged.  ``publish`` observes every received chunk on
    EITHER route (the fan-out source's broadcast tap).

    ``drain_timeout`` bounds every reply-stall wait: when the reply
    stream makes no write progress for that many seconds — whether the
    stall surfaces in the end-of-session drain join or mid-session in
    the digest-flush backpressure wait — the encoder is destroyed and
    ``close_write`` invoked (best-effort) so the connection tears down
    instead of leaking a parked thread per stalled client; ``None``
    waits forever (the pre-round-6 behavior).  In hub mode the deadline
    is PER SESSION by construction: each connection's thread owns its
    own progress clock, so one draining session's deadline neither
    extends nor cuts short another's.

    ``hub`` (a :class:`~.hub.ReplicationHub`) switches this session
    onto the shared device engine: the decoder's digest work registers
    under ``session_key`` and coalesces with every co-resident
    session's into single XLA dispatches, completions routing back
    here by key.  Admission rejection (:class:`~.hub.HubBusy`) returns
    a structured ``{"ok": False, "rejected": True, ...}`` record
    without consuming any wire bytes; a mid-session shed
    (:class:`~.hub.SessionShed`) tears this session down like any
    other session-fatal error — co-residents never notice either.

    The decoder is ALWAYS the digest-capable ``backend='tpu'`` one —
    the plain host :class:`Decoder` has no digest surface and would
    make the sidecar silently useless.  Which engine actually hashes
    (device batches vs the native host engine) is the routing layer's
    call; the CLI's ``--backend host`` forces the host engine via the
    routing override env var (see :func:`main`) — process-wide, which
    is why the override does not live here.
    """
    from . import decode, encode

    hub_session = None
    if hub is not None:
        from .hub import HubBusy

        try:
            hub_session = hub.register(session_key)
        except HubBusy as e:
            # structured rejection, bounded state: no decoder, no reply
            # thread, no queue growth — the client observes EOF
            out = {"changes": 0, "blobs": 0, "bytes": 0, "digests": 0,
                   "ok": False, "rejected": True,
                   "sessions": e.sessions, "parked_bytes": e.parked_bytes}
            if close_write is not None:
                try:
                    # a shutdown syscall (every caller's close_write is
                    # shutdown/os.close) — bounded
                    # datlint: allow-callback-escape
                    close_write()
                except OSError:
                    pass
            if _OBS.on:
                _emit("sidecar.session", **out)
            return out

    enc = encode()  # reply stream: plain host encoder (digest payloads)
    if hub_session is not None:
        dec = decode(backend="tpu", pipeline=hub_session)
    else:
        dec = decode(backend="tpu")
    stats = {"digests": 0}
    # fleet-plane watermarks: this session's receive cursors, one link
    # per connection (untracked on exit — dead sessions vanish)
    wm_link = session_key if session_key else "stdio"
    dec.watermark(wm_link)
    # wire cost plane (ISSUE 20): name this session's ledger link after
    # the same key the watermark plane uses, so `obs fleet` can join
    # cost rows against cursors without a translation table.  Plain
    # attribute writes — the boards only see them when the lit helpers
    # run, so the dark path is untouched.
    enc.cost_link = wm_link
    dec.cost_link = wm_link

    # reply write progress, shared by every stall check: refreshed each
    # time a reply byte actually reaches the transport
    progress = {"t": time.monotonic()}

    def _stalled(now: float) -> bool:
        return (drain_timeout is not None
                and now - progress["t"] > drain_timeout)

    def _teardown_stalled() -> None:
        # the drain deadline fired: the client stopped reading its reply
        # (ADVICE.md round 5 low) — record it as a structured stall
        # event so the leak class is visible at runtime, then tear down
        if _OBS.on:
            _M_STALLS.inc()
            _emit("sidecar.stall", kind="reply-drain",
                  seconds=drain_timeout, reply_bytes=enc.bytes)
        enc.destroy(TimeoutError(
            f"reply stream stalled for {drain_timeout}s"))
        if close_write is not None:
            try:
                # unblocks a sender parked in a socket write (shutdown
                # wakes it with EPIPE); best-effort — the caller's
                # close is the backstop
                close_write()
            except OSError:
                pass

    def on_digest(kind: str, seq: int, digest: bytes) -> None:
        stats["digests"] += 1
        flushed = threading.Event()
        below_hw = enc.change({
            "key": f"{kind}-{seq}",
            "change": seq,
            "from": 0,
            "to": 1,
            "value": digest,
            "subset": DIGEST_SUBSET_CHANGE if kind == "change"
            else DIGEST_SUBSET_BLOB,
        }, on_flush=flushed.set)
        if not below_hw:
            # reply-side backpressure: this callback runs on the decoder's
            # consume path, so blocking here stalls request consumption —
            # the client that won't read its reply eventually can't send
            # either, and reply memory stays bounded by the high-water
            # mark instead of growing with the session.  Same stall
            # deadline as the drain join below: a client that parked the
            # reply mid-session would otherwise hang this wait forever
            # and the drain teardown could never be reached
            progress["t"] = time.monotonic()  # stall measured from HERE:
            # a long reply-quiet stretch before this wait (one huge blob,
            # digests batched) is not the client's fault
            while not (flushed.wait(0.1) or enc.destroyed):
                if _stalled(time.monotonic()):
                    _teardown_stalled()
                    break

    # on_digest's flush wait is bounded (flushed.wait(0.1) ladder with
    # the drain-timeout teardown above) — audited, ISSUE 17 satellite
    # datlint: allow-callback-escape
    dec.on_digest(on_digest)
    # change/blob handlers stay unregistered: the decoder's defaults
    # (drop changes, drain blobs) are exactly the sidecar's behavior,
    # with no per-frame ack bookkeeping
    # all digests are flushed (and encoded) before this hook runs;
    # finalizing the reply inside it seals the ordering guarantee
    dec.finalize(lambda done: (enc.finalize(), done()))
    # a malformed request must tear down the reply sender too (EOF at
    # the client), and a reply-side failure must stop consuming;
    # destroy() flips state and wakes watchers — never blocks
    # datlint: allow-callback-escape
    dec.on_error(lambda _e: enc.destroy())
    # datlint: allow-callback-escape
    enc.on_error(lambda _e: None if dec.destroyed else dec.destroy())

    # pump route selection (ISSUE 14): fds + a native route take the
    # batched-syscall loops; anything else is the Python reference pump
    native_route = ((rx_fd is not None or tx_fd is not None)
                    and session_pump.effective_pump_route() == "native")

    def _write(data) -> None:
        write_bytes(data)
        progress["t"] = time.monotonic()  # reply byte reached the client

    def _mark_progress() -> None:
        progress["t"] = time.monotonic()  # reply batch reached the client

    def _send() -> None:
        try:
            if native_route and tx_fd is not None:
                session_pump.send_pump(enc, tx_fd, close=close_write,
                                       on_progress=_mark_progress)
            else:
                send_over(enc, _write, close_write)
        except Exception as e:  # EPIPE/ECONNRESET from a vanished client
            if not enc.destroyed:
                enc.destroy(e)
            if not dec.destroyed:
                dec.destroy(e)

    if publish is not None and not (native_route and rx_fd is not None):
        # the Python route's broadcast tap: wrap the reader so the
        # published stream is byte-identical to the native pump's tap
        def read_bytes(n, _r=read_bytes):
            data = _r(n)
            if data:
                publish(data)
            return data

    sender = threading.Thread(target=_send, name="sidecar-send",
                              daemon=True)
    sender.start()
    try:
        # span brackets the request-consumption phase; the per-frame
        # wire-offset instants the decoder records nest under it
        with _trace_span("sidecar.session.recv"):
            if native_route and rx_fd is not None:
                session_pump.recv_pump(dec, rx_fd, tap=publish)
            else:
                recv_over(dec, read_bytes)
    except Exception as e:  # ECONNRESET etc.: transport died mid-read —
        # or, in hub mode, SessionShed/HubError surfacing from the
        # decoder's digest submits: session-fatal either way, and the
        # destroy cascade below keeps it THIS session's problem
        if not dec.destroyed:
            dec.destroy(e)
        if not enc.destroyed:
            enc.destroy(e)
    if dec.destroyed and not enc.destroyed:
        enc.destroy()
    if enc.destroyed:
        # the sender may sit in a blocking write to a dead peer; the
        # caller's socket close unblocks it — don't wait on it here
        sender.join(timeout=5)
    else:
        # healthy path: the reply is still draining to the client;
        # truncating it early would corrupt a correct session
        # mid-frame, but a bare join() would park this thread forever
        # behind a client that stopped reading (ADVICE.md round 5) —
        # so join in bounded steps and tear the session down once the
        # reply makes no progress for drain_timeout seconds
        progress["t"] = time.monotonic()  # idle clock starts at drain
        while True:
            sender.join(timeout=_DRAIN_POLL)
            if not sender.is_alive():
                break
            if _stalled(time.monotonic()):
                _teardown_stalled()
                sender.join(timeout=5)
                break
    out = {
        "changes": dec.changes,
        "blobs": dec.blobs,
        "bytes": dec.bytes,
        "digests": stats["digests"],
        "ok": (dec.finished and not dec.destroyed and not enc.destroyed
               and not sender.is_alive()),
    }
    if hub_session is not None:
        out["session"] = hub_session.key
        out["shed"] = hub_session.shed_reason
        # release the hub slot LAST: queued work is dropped, in-flight
        # completions discard on arrival — a torn-down session cannot
        # park bytes against the shared budget
        hub_session.close()
    _WATERMARKS.untrack(wm_link)
    if _OBS.on:
        _M_SESSIONS.inc()
        _emit("sidecar.session", **out)
    return out


# a refusal goes to a peer we are about to drop: it must never park the
# session thread on sendall against a receiver that stopped draining
# (the blocking-reachability certifier's first true positive — the
# kernel buffer absorbs the ~200-byte record instantly from any healthy
# peer, so the bound only ever fires on a dead one)
_REFUSAL_SEND_TIMEOUT = 5.0


def _send_refusal(conn: socket.socket, out: dict) -> None:
    """Best-effort structured-refusal write with a hard bound.

    ``settimeout`` flips the socket to timeout mode for the remaining
    sends; that is fine here — every caller drops ``conn`` right after.
    ``socket.timeout`` is an ``OSError`` subclass, so the one except
    clause covers refused, reset, AND wedged receivers.
    """
    try:
        conn.settimeout(_REFUSAL_SEND_TIMEOUT)
        # bounded by the settimeout above (invisible to the certifier,
        # which reads call shapes, not socket modes).
        # datlint: allow-blocking-reachable(socket)
        conn.sendall((json.dumps(out) + "\n").encode())
        conn.shutdown(socket.SHUT_WR)
    except OSError:
        pass


def run_subscriber(conn: socket.socket, fanout, key: str) -> dict:
    """Serve one fan-out subscriber connection (ISSUE 9): attach the
    socket as a downstream peer of the shared :class:`BroadcastLog` and
    stream the broadcast until the sealed log is fully delivered or the
    peer is shed.  The subscriber never decodes and never hashes — the
    digest work happened ONCE on the source session.

    A joiner asking below the retained window gets a structured
    ``{"snapshot_needed": true, "retained": [start, end]}`` record and
    EOF — plus a ``"hint"`` naming the snapshot bootstrap port when
    the deployment serves it (``--snapshot``, ISSUE 12), so the joiner
    redirects without out-of-band config; admission rejection gets
    ``{"rejected": true}`` — bounded
    state, never queue growth (the hub's contract, restated for peers).
    A subscriber that SENDS data is a misrouted source (it raced a
    connection holding the source claim): it gets a structured
    ``{"not_source": true}`` record and EOF instead of having its
    uploaded session silently discarded.
    """
    from .fanout import FanoutBusy, SnapshotNeeded

    try:
        # a wire subscriber needs the stream FROM BYTE 0 to parse it;
        # once the log trimmed past 0 only a snapshot can help
        peer = fanout.attach_peer(key, fd=conn.fileno(), offset=0)
    except SnapshotNeeded as e:
        out = {"fanout_peer": key, "ok": False, "snapshot_needed": True,
               "retained": list(e.retained)}
        if e.hint is not None:
            # the deployment serves the snapshot bootstrap (ISSUE 12):
            # the refusal record carries the redirect — port +
            # capability — so the joiner needs no out-of-band config
            out["hint"] = dict(e.hint)
        _send_refusal(conn, out)
        if _OBS.on:
            _emit("sidecar.session", **out)
        return out
    except FanoutBusy as e:
        out = {"fanout_peer": key, "ok": False, "rejected": True,
               "peers": e.peers, "max_peers": e.max_peers}
        # the structured record IS the rejection: a bare EOF would be
        # indistinguishable from an empty sealed broadcast
        _send_refusal(conn, out)
        if _OBS.on:
            _emit("sidecar.session", **out)
        return out
    try:
        # bounded waits interleaved with an EOF probe on the (non-
        # blocking) socket: a subscriber that disconnects while the
        # broadcast is idle would otherwise never surface an EPIPE —
        # no bytes are in flight to it — and its peer slot plus this
        # thread would leak until new bytes happened to flow
        done = False
        not_source = False
        while True:
            if peer.wait_done(timeout=0.5):
                done = True
                break
            if peer.shed_reason is not None:
                break
            try:
                # bounded: the fd is O_NONBLOCK (attach_peer's dup
                # shares the open file description, and the fan-out
                # flips it for its writev path) — a silent subscriber
                # answers EAGAIN immediately, never a sleeping read
                # datlint: allow-blocking-reachable(socket)
                probe = conn.recv(4096)
            except (BlockingIOError, InterruptedError):
                continue  # still connected, nothing sent (the normal)
            except OSError:
                break
            if probe == b"":
                break  # client went away: release the slot
            # a subscriber has nothing to say — inbound bytes mean a
            # SOURCE got routed here (it raced a connection holding
            # the source claim).  Fail LOUDLY with a structured record
            # instead of silently discarding its uploaded session.
            not_source = True
            break
        stats = peer.stats()
    finally:
        peer.close()
    if not_source:
        out = {"fanout_peer": key, "ok": False, "not_source": True,
               "detail": "subscriber connections must not send data; "
                         "the broadcast source slot was already claimed "
                         "— reconnect to retry as source"}
        _send_refusal(conn, out)
        if _OBS.on:
            _emit("sidecar.session", **out)
        return out
    try:
        conn.shutdown(socket.SHUT_WR)  # subscriber observes clean EOF
    except OSError:
        pass
    out = {"fanout_peer": key, "sent_bytes": stats["sent_bytes"],
           "shed": stats["shed"], "ok": done and stats["shed"] is None}
    if _OBS.on:
        _M_SESSIONS.inc()
        _emit("sidecar.session", **out)
    return out


def run_reconcile_session(conn_read, conn_write, close_write,
                          replica, peer: str = "?") -> dict:
    """Serve one anti-entropy session (ISSUE 10): the client is the
    reconcile *initiator* streaming coded-symbol frames; this side
    responds from ``replica`` (the ``--reconcile LOGFILE`` change log)
    and the two exchange exactly the differing records.  Connecting to
    a ``--reconcile`` sidecar IS the out-of-band capability
    advertisement (WIRE.md): both directions speak
    ``CAP_RECONCILE | CAP_CHANGE_BATCH``.

    A failed decode (corrupt stream, exhausted symbols) surfaces as the
    driver's ONE structured ProtocolError; the client observes the FAIL
    frame + EOF, never a hang."""
    from .runtime.reconcile_driver import run_responder
    from .wire.framing import ProtocolError

    try:
        stats = run_responder(replica, conn_read, conn_write,
                              close_write=close_write)
        out = {"reconcile": True, "ok": stats["ok"],
               "symbols": stats["symbols"], "rounds": stats["rounds"],
               "records_sent": stats["records_sent"],
               "records_received": len(stats["received"])}
    except (ProtocolError, OSError) as e:
        out = {"reconcile": True, "ok": False, "peer": peer,
               "error": f"{type(e).__name__}: {e}"}
    if _OBS.on:
        _M_SESSIONS.inc()
        _emit("sidecar.session", **out)
    return out


def run_replica_session(conn_read, conn_write, close_write,
                        node, peer: str = "?") -> dict:
    """Serve one gossip responder session (ISSUE 15): like
    ``--reconcile``, but against the LIVE :class:`~.cluster.ReplicaNode`
    — records the initiator ships are absorbed into the node's log, so
    every inbound session advances convergence instead of answering
    from a frozen file."""
    from .cluster import serve_responder_session
    from .wire.framing import ProtocolError

    try:
        stats = serve_responder_session(node, conn_read, conn_write,
                                        close_write=close_write)
        out = {"replica": node.key, "ok": stats["ok"],
               "symbols": stats["symbols"], "rounds": stats["rounds"],
               "records_sent": stats["records_sent"],
               "applied": stats["applied"]}
    except (ProtocolError, OSError) as e:
        out = {"replica": node.key, "ok": False, "peer": peer,
               "error": f"{type(e).__name__}: {e}"}
    if _OBS.on:
        _M_SESSIONS.inc()
        _emit("sidecar.session", **out)
    return out


def load_replica_node(path: str, key: str):
    """Build the ``--replica`` gossip node from a change-log wire file
    (same input contract as ``--reconcile``; an absent/empty file is a
    cold replica that converges entirely from its peers)."""
    from .cluster import ReplicaNode

    wire = b""
    if os.path.exists(path):
        with open(path, "rb") as f:
            wire = f.read()
    # delivered_form: the live mesh's record identity is the per-record
    # DELIVERED materialization (absent optionals as ''/b'') — the form
    # every decoder delivery produces, so shipped records keep their
    # digests and the mesh actually reaches diff 0 (see ReplicaNode)
    return ReplicaNode(key, wire, delivered_form=True)


def load_reconcile_replica(path: str):
    """Build the sidecar's replica from a change-log wire file
    (per-record and/or ChangeBatch frames — ``replay.replay_log``'s
    input contract)."""
    from .runtime.reconcile_driver import RatelessReplica

    with open(path, "rb") as f:
        return RatelessReplica(f.read())


def run_snapshot_session(conn_read, conn_write, close_write,
                         source, peer: str = "?") -> dict:
    """Serve one snapshot bootstrap session (ISSUE 12): the client is a
    *joiner* — it receives the manifest, reconciles its chunk set (or
    WANTs everything when cold), and is streamed exactly the chunks it
    is missing from the shared :class:`~.runtime.snapshot_driver.
    SnapshotSource` (hashed ONCE, however many joiners connect).
    Connecting to a ``--snapshot`` sidecar IS the out-of-band
    capability advertisement (WIRE.md): both directions speak
    ``CAP_SNAPSHOT``.

    A failed session (corrupt stream, chunk budget, byzantine WANT)
    surfaces as the driver's ONE structured ProtocolError; the client
    observes the FAIL frame + EOF, never a hang."""
    from .runtime.snapshot_driver import run_snapshot_responder
    from .wire.framing import ProtocolError

    try:
        stats = run_snapshot_responder(source, conn_read, conn_write,
                                       close_write=close_write)
        out = {"snapshot": True, "ok": stats["ok"],
               "cold": stats["cold"], "chunks_sent": stats["chunks_sent"],
               "chunk_bytes_sent": stats["chunk_bytes_sent"],
               "symbols": stats["symbols"], "rounds": stats["rounds"]}
    except (ProtocolError, OSError) as e:
        out = {"snapshot": True, "ok": False, "peer": peer,
               "error": f"{type(e).__name__}: {e}"}
    if _OBS.on:
        _M_SESSIONS.inc()
        _emit("sidecar.session", **out)
    return out


def load_snapshot_source(path: str, wire_offset: int = 0):
    """Materialize the ``--snapshot DATAFILE`` dataset once: CDC cuts +
    fused digests + manifest, shared by every responder session
    (hash-once across the whole flash crowd)."""
    from .runtime.snapshot_driver import SnapshotSource

    with open(path, "rb") as f:
        return SnapshotSource(f.read(), wire_offset=wire_offset)


class SnapshotListener:
    """The dedicated snapshot bootstrap port (the ``--fanout`` +
    ``--snapshot`` composition): a tiny accept loop serving each
    connection as one responder session off the shared source.  The
    bound ``port`` rides the fan-out's ``snapshot_hint``, so the
    structured snapshot-needed record a trimmed-past subscriber gets
    names exactly where to bootstrap from."""

    def __init__(self, source, host: str, port: int = 0):
        self.source = source
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(8)
        # kernel-bounded accept (ISSUE 17 satellite): the periodic
        # socket.timeout below re-checks liveness instead of parking
        # the accept thread forever on a silent listener
        self._srv.settimeout(1.0)
        self.port = self._srv.getsockname()[1]
        self._served = 0
        self._thread = threading.Thread(
            target=self._loop, name="sidecar-snapshot", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while True:
            try:
                # bounded by the settimeout(1.0) set at construction
                # datlint: allow-blocking-reachable(socket)
                conn, peer = self._srv.accept()
            except socket.timeout:
                continue  # periodic liveness re-check
            except OSError:
                return  # closed: the daemon is shutting down
            self._served += 1
            n = self._served

            def _one(conn=conn, peer=peer, n=n):
                try:
                    rd, wr = session_pump.io_for_socket(conn)
                    stats = run_snapshot_session(
                        rd, wr,
                        lambda: conn.shutdown(socket.SHUT_WR),
                        self.source, peer=f"{peer[0]}:{peer[1]}")
                    print(f"sidecar: snapshot {peer} {stats}",
                          file=sys.stderr, flush=True)
                finally:
                    conn.close()

            threading.Thread(target=_one, name=f"sidecar-snap-{n}",
                             daemon=True).start()

    def close(self) -> None:
        try:
            self._srv.close()
        except OSError:
            pass


def serve_stdio(drain_timeout: float | None = DEFAULT_DRAIN_TIMEOUT) -> dict:
    """One session over stdin/stdout (logs go to stderr only)."""
    # close_write can fire from the session thread (drain-timeout
    # teardown) while the sender thread sits mid-write on fd 1, so a
    # bare os.close(1) has a reuse hazard: once fd 1 is free, any
    # thread's next open() can be handed 1, and _write_all's
    # partial-write retry loop would then write reply bytes into an
    # unrelated descriptor.  dup2 of /dev/null atomically releases the
    # pipe write end (the reader still sees EOF) while keeping fd 1
    # occupied — a late retry write lands in /dev/null instead.  A
    # writer currently blocked in write(2) is NOT woken by this (unlike
    # the TCP twin's shutdown-EPIPE); it unblocks only when the peer
    # reads or exits, which the bounded drain join tolerates.  Once-only
    # (transport.once) so the second caller (send_over's finally)
    # doesn't reopen devnull.
    from .session.transport import once

    def _swap_stdout_for_devnull() -> None:
        devnull = os.open(os.devnull, os.O_WRONLY)
        os.dup2(devnull, 1)
        os.close(devnull)

    _close_stdout = once(_swap_stdout_for_devnull)

    stats = run_session(
        read_bytes=lambda n: os.read(0, n),
        write_bytes=lambda d: _write_all(1, d),
        close_write=_close_stdout,
        drain_timeout=drain_timeout,
        rx_fd=0, tx_fd=1,
    )
    print(f"sidecar: stdio session {stats}", file=sys.stderr, flush=True)
    return stats




def serve_tcp(host: str, port: int,
              max_sessions: int | None = None,
              ready_cb=None,
              drain_timeout: float | None = DEFAULT_DRAIN_TIMEOUT,
              retry_policy=None, hub=None, fanout=None,
              reconcile_replica=None, snapshot_source=None,
              replica_node=None) -> None:
    """Accept loop: one concurrent session per connection.

    ``max_sessions`` bounds the loop for tests; ``ready_cb(port)`` fires
    once the socket is bound+listening (the test/race-free handshake).

    ``hub`` (ISSUE 8): a shared :class:`~.hub.ReplicationHub` every
    accepted session registers with — one device pipeline multiplexed
    across all concurrent connections, admission-controlled, with
    per-session keys ``c<n>:<peer>`` in the stats breakdown.

    ``fanout`` (ISSUE 9): a shared :class:`~.fanout.FanoutServer`.  The
    first connection to CLAIM the source slot is the broadcast
    *source*: it is served like any normal session (decoded once —
    with ``hub`` set its digest work rides the shared engine — and its
    digest reply streamed back), while every wire byte it sends is
    also published into the shared :class:`~.fanout.BroadcastLog`.  A
    claimant that closes without publishing a byte (healthcheck, port
    scan) RELEASES the claim — the next connection can be the source.
    Every other connection is a subscriber: it receives the source's
    raw wire bytes via the zero-copy windowed ``writev`` fan-out path,
    keyed ``p<n>:<peer>`` in the stats breakdown.  Digest/hash cost is
    O(1) in subscribers.

    ``retry_policy`` (a :class:`~.session.reconnect.BackoffPolicy`, CLI
    flags ``--max-retries`` / ``--backoff-base``) governs the daemon's
    transient-failure behavior: binding retries through a lingering
    ``EADDRINUSE`` (the restart-while-old-socket-drains race) and the
    accept loop rides out bursts of ``EMFILE``/``ECONNABORTED`` with
    backoff instead of crashing the daemon; sustained failure surfaces
    as one structured ProtocolError (see ROBUSTNESS.md).
    """
    from .session.reconnect import BackoffPolicy, retrying

    policy = retry_policy if retry_policy is not None else BackoffPolicy()

    def _bind() -> socket.socket:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind((host, port))
            s.listen(8)
        except OSError:
            s.close()
            raise
        return s

    srv = retrying(_bind, policy, retry_on=(OSError,),
                   describe=f"bind {host}:{port}")
    # fan-out source election: the source slot is CLAIMED, not simply
    # "connection #1" — a stray first connection that closes without
    # publishing a byte (load-balancer healthcheck, port scan) releases
    # the claim instead of sealing an empty log and bricking the
    # broadcast for the daemon's lifetime
    src_claim = {"taken": False}
    src_lock = threading.Lock()
    bound = srv.getsockname()[1]
    print(f"sidecar: listening on {host}:{bound}",
          file=sys.stderr, flush=True)
    if ready_cb is not None:
        # one-shot bound-port handshake, fired BEFORE any session
        # exists — a slow callback delays startup, never a session
        # datlint: allow-callback-escape
        ready_cb(bound)
    served = 0
    try:
        while max_sessions is None or served < max_sessions:
            # transient accept failures (fd exhaustion, aborted
            # handshakes) back off instead of killing the daemon; each
            # retrying() call is one fresh consecutive-failure budget,
            # so a successful accept resets the count
            conn, peer = retrying(srv.accept, policy, retry_on=(OSError,),
                                  describe="accept")
            served += 1

            def _one(conn=conn, peer=peer, n=served):
                try:
                    if snapshot_source is not None:
                        # bootstrap mode (ISSUE 12): every connection is
                        # one joiner served off the shared materialized
                        # source (read-only after construction: sessions
                        # never step on each other, hashing happened
                        # once).  The --fanout composition does NOT pass
                        # this — there the snapshot protocol lives on
                        # its own SnapshotListener port and this loop
                        # keeps serving the broadcast.
                        rd, wr = session_pump.io_for_socket(conn)
                        stats = run_snapshot_session(
                            rd, wr,
                            lambda: conn.shutdown(socket.SHUT_WR),
                            snapshot_source,
                            peer=f"{peer[0]}:{peer[1]}")
                        print(f"sidecar: {peer} {stats}", file=sys.stderr,
                              flush=True)
                        return
                    if replica_node is not None:
                        # gossip replica mode (ISSUE 15): every
                        # connection is one reconcile initiator against
                        # the LIVE node — received records are absorbed,
                        # so inbound sessions advance convergence
                        rd, wr = session_pump.io_for_socket(conn)
                        stats = run_replica_session(
                            rd, wr,
                            lambda: conn.shutdown(socket.SHUT_WR),
                            replica_node,
                            peer=f"{peer[0]}:{peer[1]}")
                        print(f"sidecar: {peer} {stats}", file=sys.stderr,
                              flush=True)
                        return
                    if reconcile_replica is not None:
                        # anti-entropy mode (ISSUE 10): every connection
                        # is one reconcile initiator against the shared
                        # replica (read-only state: sessions never step
                        # on each other)
                        rd, wr = session_pump.io_for_socket(conn)
                        stats = run_reconcile_session(
                            rd, wr,
                            lambda: conn.shutdown(socket.SHUT_WR),
                            reconcile_replica,
                            peer=f"{peer[0]}:{peer[1]}")
                        print(f"sidecar: {peer} {stats}", file=sys.stderr,
                              flush=True)
                        return
                    is_source = False
                    if fanout is not None and not fanout.log.sealed:
                        with src_lock:
                            if not src_claim["taken"]:
                                src_claim["taken"] = True
                                is_source = True
                    if fanout is not None and not is_source:
                        stats = run_subscriber(
                            conn, fanout, key=f"p{n}:{peer[0]}:{peer[1]}")
                    elif fanout is not None:
                        # the source session: every wire byte it sends
                        # is published into the broadcast log as it is
                        # consumed (the pump's tap on either route);
                        # EOF (or teardown) seals the log so
                        # subscribers complete
                        try:
                            stats = run_session(
                                read_bytes=conn.recv,
                                write_bytes=conn.sendall,
                                close_write=lambda: conn.shutdown(
                                    socket.SHUT_WR),
                                drain_timeout=drain_timeout,
                                hub=hub,
                                session_key=f"c{n}:{peer[0]}:{peer[1]}",
                                rx_fd=conn.fileno(), tx_fd=conn.fileno(),
                                publish=fanout.publish,
                            )
                        finally:
                            if fanout.log.end > fanout.log.start:
                                fanout.seal()
                            else:
                                # nothing published: a probe connection,
                                # not the feed — give the slot back
                                with src_lock:
                                    src_claim["taken"] = False
                    else:
                        stats = run_session(
                            read_bytes=conn.recv,
                            write_bytes=conn.sendall,
                            close_write=lambda: conn.shutdown(
                                socket.SHUT_WR),
                            drain_timeout=drain_timeout,
                            hub=hub,
                            session_key=f"c{n}:{peer[0]}:{peer[1]}",
                            rx_fd=conn.fileno(), tx_fd=conn.fileno(),
                        )
                    print(f"sidecar: {peer} {stats}", file=sys.stderr,
                          flush=True)
                finally:
                    conn.close()

            threading.Thread(target=_one, name=f"sidecar-{peer}",
                             daemon=True).start()
    finally:
        srv.close()


class StatsEmitter:
    """Periodic registry snapshots on a file descriptor.

    The ``--stats-fd`` machinery: a daemon thread dumps one snapshot
    every ``interval`` seconds; :meth:`kick` forces an immediate dump
    (the SIGUSR1 one-shot — the handler just sets an event, so the dump
    work never runs in signal context).  ``fmt="json"`` (default)
    writes self-contained JSON lines, so a supervisor can ``tail -f``
    the pipe and parse each line independently; ``fmt="prom"``
    (``--stats-format prom``) writes Prometheus text-exposition blocks
    (``obs.metrics.to_prom_text``) instead — each dump is one complete
    scrape body, for a node-exporter-style textfile collector.
    """

    def __init__(self, fd: int, interval: float = DEFAULT_STATS_INTERVAL,
                 fmt: str = "json"):
        if fmt not in ("json", "prom"):
            raise ValueError(f"unknown stats format {fmt!r}")
        self._fd = fd
        # the EAGAIN/deadline machinery in dump_once only ever engages
        # on a NONBLOCKING fd: on a blocking pipe with a stopped
        # consumer, os.write parks the emitter thread forever (stop()
        # then reports False and the process leaks the thread).  Flip
        # the fd up front so the 2 s grace bound is real — the
        # blocking-reachability certifier's second true positive.
        try:
            os.set_blocking(fd, False)
        except OSError:
            pass  # closed/odd fd: the first write will surface it
        self._fmt = fmt
        self._interval = interval
        self._wake = threading.Event()
        self._stopped = False
        self._dead = False  # fd failed or a line tore: never write again
        # monotonic per-emitter line sequence (ISSUE 11): every dump
        # ATTEMPT consumes a number, so a file-based fleet target can
        # detect dropped lines (EAGAIN skip, torn-line latch) as seq
        # gaps instead of silently reading a thinner history
        self._emit_seq = 0
        self._thread = threading.Thread(
            target=self._run, name="sidecar-stats", daemon=True)

    def start(self) -> "StatsEmitter":
        self._thread.start()
        return self

    def kick(self) -> None:
        """Request an immediate snapshot dump (signal-safe: only sets
        an event; the emitter thread does the I/O)."""
        self._wake.set()

    def stop(self) -> bool:
        """Stop the emitter thread; returns True once it has actually
        exited.  False means it is still blocked (e.g. inside a write
        to a pipe nobody drains) — the caller must NOT write the fd
        itself then, or the two writers interleave past PIPE_BUF."""
        self._stopped = True
        self._wake.set()
        self._thread.join(timeout=5)
        return not self._thread.is_alive()

    def dump_once(self) -> bool:
        """Write one snapshot line now (from the calling thread);
        returns False when the fd is dead or persistently blocked.
        Once a record TORE (partial write, then the pipe stayed full
        past the grace period) the emitter latches dead: appending any
        later record to the torn fragment would merge two lines and
        break the one-JSON-object-per-line contract."""
        import errno

        if self._dead:
            return False
        seq = self._emit_seq
        self._emit_seq += 1
        if self._fmt == "prom":
            body = snapshot_stats_prom()
        else:
            snap = snapshot_stats()
            snap["emit_seq"] = seq
            body = json.dumps(snap) + "\n"
        line = body.encode("utf-8")
        view = memoryview(line)
        deadline = time.monotonic() + 2.0
        while view:
            try:
                # bounded: __init__ flipped the fd nonblocking, so this
                # either progresses or raises EAGAIN into the deadline
                # arm below.  datlint: allow-blocking-reachable(os-io)
                view = view[os.write(self._fd, view):]
            except OSError as e:
                # EAGAIN is a momentarily-full pipe, not a dead one: a
                # bounded retry finishes the record (a half-written
                # line would corrupt the JSONL stream).  Skip the tick
                # if nothing was written yet; a pipe still full after
                # the grace period counts as a dead consumer.
                if e.errno in (errno.EAGAIN, errno.EWOULDBLOCK):
                    if time.monotonic() < deadline:
                        time.sleep(0.01)
                        continue
                    if len(view) == len(line):
                        return True  # clean skip: nothing written yet
                self._dead = True  # torn line or hard error
                return False
        return True

    def _run(self) -> None:
        while not self._stopped:
            self._wake.wait(self._interval)
            self._wake.clear()
            if self._stopped:
                return
            if not self.dump_once():
                return  # consumer closed the stats pipe: stop quietly


def snapshot_stats() -> dict:
    """One self-describing stats record: the full metrics registry
    snapshot plus event-ring health and per-site jit-cache traffic
    (the recompile sentinel: a long-lived sidecar recompiling per
    request is the device-path pathology --stats-fd exists to catch).
    In hub mode the record also carries the per-session ``sessions``
    breakdown and the hub's aggregate state, keyed by session — the
    supervisor-visible answer to "which peer is parking bytes".
    JSON-able as-is."""
    # device memory gauges are sampled per snapshot (never initialises a
    # backend): peak bytes_in_use is what says a batch cap fits HBM
    obs_device.sample_device_gauges()
    out = {
        "ts": time.time(),
        "monotonic": time.monotonic(),
        "metrics": obs_metrics.snapshot(),
        "events_dropped": obs_events.EVENTS.dropped,
        "jit_sites": obs_device.SENTINEL.snapshot(),
        # which blake2b kernel each block-count bucket reached, and
        # what its padding cost (engine is chosen per bucket)
        "blake2b_buckets": obs_device.BUCKETS.snapshot(),
        # the fleet plane's join input (ISSUE 11): per-link wire
        # cursors + append marks — the SAME dict /snapshot serves
        "watermarks": _WATERMARKS.snapshot(),
        # the active wire-pump route + syscall tier (ISSUE 14): which
        # byte mover this daemon's sessions actually ride
        "pump": session_pump.probe_caps(),
        # which engine hashes digest batches here and on what device
        # (platform / device_kind / device_count as jax reports them):
        # a sidecar that lost its chip must be visible from outside
        "device": _DEVICE_RECORD,
    }
    if _ACTIVE_HUB is not None:
        out["hub"] = _ACTIVE_HUB.snapshot()
        out["sessions"] = _ACTIVE_HUB.sessions_snapshot()
    if _ACTIVE_FANOUT is not None:
        out["fanout"] = _ACTIVE_FANOUT.snapshot()
        out["peers"] = _ACTIVE_FANOUT.peers_snapshot()
    if _ACTIVE_GOSSIP is not None:
        # replica mode (ISSUE 15): gossip round / repair / quarantine
        # counters + the content digest — what `obs fleet` derives the
        # per-replica rounds-behind convergence column from
        out["gossip"] = _ACTIVE_GOSSIP.snapshot()
        # the mesh convergence plane (ISSUE 19): per-link exchange
        # provenance + divergence watermarks + frontier — the fleet
        # matrix join input.  Empty boards (plane dark) are omitted so
        # the loud-failure rule in `obs fleet` can tell "plane off"
        # from "no exchanges yet".
        prop = _PROPAGATION.snapshot()
        if prop["links"] or prop["frontier"]:
            out["propagation"] = prop
    # the wire cost plane (ISSUE 20): per-link byte ledger + goodput /
    # overhead / amplification watermarks.  Presence-gated like the
    # propagation board above — an empty ledger (plane dark, or lit but
    # no traffic yet) is omitted entirely, so `obs fleet` can apply the
    # loud-failure rule to cost SLO keys instead of averaging zeros.
    wc = _WIRECOST.snapshot()
    if wc["links"] or wc["amplification"]:
        out["wirecost"] = wc
    if _ACTIVE_EDGE is not None:
        # edge mode (ISSUE 17): the unified session-table aggregate —
        # per-QoS-class and per-kind session counts, admission/shed
        # tallies, the active pump route
        out["edge"] = _ACTIVE_EDGE.snapshot()
    # staged health rides every snapshot record, so file-based fleet
    # targets (tailing --stats-fd lines) can evaluate require_healthz
    # — not just endpoint targets with a /healthz route
    out["healthz"] = obs_http.default_healthz(_active_admission_fn())
    return out


def _active_admission_fn():
    """The lock-free admission view of whichever shared engine this
    daemon runs.  The edge wins when set (ISSUE 17): its admission
    stage COMPOSES the hub's (edge table state + the hub's open/parked
    verdict), so /healthz reports the decision connections actually
    face; otherwise hub wins over fanout (fanout composes with it as
    the broadcast layer, admission is the hub's)."""
    if _ACTIVE_EDGE is not None:
        return _ACTIVE_EDGE.admission_state
    if _ACTIVE_HUB is not None:
        return _ACTIVE_HUB.admission_state
    if _ACTIVE_FANOUT is not None:
        return _ACTIVE_FANOUT.admission_state
    return None


def snapshot_stats_prom() -> str:
    """The same stats record in Prometheus text exposition: the
    registry via ``to_prom_text`` plus ring-health gauges."""
    extra = (
        "# TYPE dat_obs_events_dropped gauge\n"
        f"dat_obs_events_dropped {obs_events.EVENTS.dropped}\n"
        "# TYPE dat_obs_spans_dropped gauge\n"
        f"dat_obs_spans_dropped {obs_tracing.SPANS.dropped}\n"
        "# TYPE dat_obs_scrape_ts gauge\n"
        f"dat_obs_scrape_ts {time.time()}\n"
    )
    return obs_metrics.to_prom_text() + extra


def _install_sigusr1(emitter: StatsEmitter) -> bool:
    """SIGUSR1 -> one-shot stats dump; returns False when not on the
    main thread (signal registration would raise there)."""
    import signal

    if threading.current_thread() is not threading.main_thread():
        return False
    signal.signal(signal.SIGUSR1, lambda _sig, _frm: emitter.kick())
    return True


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m dat_replication_protocol_tpu.sidecar",
        description="dat replication wire-protocol digest sidecar",
    )
    mode = p.add_mutually_exclusive_group(required=True)
    mode.add_argument("--stdio", action="store_true",
                      help="serve ONE session over stdin/stdout")
    mode.add_argument("--tcp", metavar="HOST:PORT",
                      help="listen and serve a session per connection")
    p.add_argument("--backend", default="tpu", choices=("tpu", "host"),
                   help="digest engine routing: 'tpu' (default) lets the "
                        "routing layer pick device batches or the host "
                        "engine; 'host' forces the host engine.  Digests "
                        "are produced either way")
    p.add_argument("--drain-timeout", type=float,
                   default=DEFAULT_DRAIN_TIMEOUT, metavar="SECONDS",
                   help="tear a session down when its reply stream makes "
                        "no progress for this long (a client that stops "
                        "reading); <= 0 waits forever "
                        f"(default: {DEFAULT_DRAIN_TIMEOUT:.0f})")
    p.add_argument("--edge", action="store_true",
                   help="event-driven edge (ISSUE 17, --tcp only): serve "
                        "every leg — hub sessions, --fanout broadcast "
                        "peers, --reconcile/--snapshot responders, "
                        "--replica gossip exchanges — from ONE epoll "
                        "session table instead of a thread per "
                        "connection (C10k), with the staged overload "
                        "ladder preserved verbatim; implies --hub for "
                        "session/broadcast-source legs (see DESIGN.md "
                        "event-driven edge)")
    p.add_argument("--hub", action="store_true",
                   help="multiplex every accepted session onto ONE shared "
                        "device engine (hub mode, --tcp only): cross-"
                        "session digest batching, admission control, "
                        "per-session QoS windows, load shedding (see "
                        "ROBUSTNESS.md overload behavior)")
    p.add_argument("--hub-max-sessions", type=int, default=1024,
                   metavar="N",
                   help="hub admission bound: concurrent session count "
                        "past which new connections get a structured "
                        "rejection (default: 1024)")
    p.add_argument("--hub-parked-budget", type=int, default=256 << 20,
                   metavar="BYTES",
                   help="hub admission + shedding bound on global parked "
                        "bytes (queued + in-flight + undelivered work; "
                        "default: 256 MiB)")
    p.add_argument("--hub-mesh", default=None, metavar="N|auto",
                   help="shard the hub's cross-session hash batch over "
                        "the device mesh: 'auto' uses every local "
                        "device, an integer pins the count (default: "
                        "single-device engine)")
    p.add_argument("--fanout", action="store_true",
                   help="broadcast mode (--tcp only): the FIRST "
                        "connection is the source session (decoded and "
                        "digested ONCE); every later connection is a "
                        "subscriber streamed the source's wire bytes "
                        "via the zero-copy windowed writev fan-out "
                        "(see DESIGN.md fan-out, ROBUSTNESS.md "
                        "peer-shed contract)")
    p.add_argument("--fanout-retention", type=int, default=64 << 20,
                   metavar="BYTES",
                   help="broadcast-log retention budget: how much wire "
                        "history stays servable for late joiners and "
                        "laggards; a peer trimmed past gets a "
                        "structured snapshot-needed record "
                        "(default: 64 MiB)")
    p.add_argument("--fanout-window", type=int, default=1 << 20,
                   metavar="BYTES",
                   help="per-peer fan-out flow-control window (bytes "
                        "in flight; sized for lossy high-latency "
                        "links; default: 1 MiB)")
    p.add_argument("--fanout-stall-timeout", type=float, default=30.0,
                   metavar="SECONDS",
                   help="shed a fan-out peer making no delivery "
                        "progress for this long (default: 30)")
    p.add_argument("--reconcile", metavar="LOGFILE", default=None,
                   help="anti-entropy mode: serve every connection as a "
                        "rateless-reconciliation responder against the "
                        "change-log wire file LOGFILE — the client "
                        "streams coded symbols, both sides exchange "
                        "exactly the differing records (O(diff) wire "
                        "bytes; see DESIGN.md anti-entropy, WIRE.md "
                        "Reconcile)")
    p.add_argument("--replica", metavar="LOGFILE", default=None,
                   help="gossip replica mode (ISSUE 15, --tcp only): "
                        "serve every connection as a live anti-entropy "
                        "responder whose received records are ABSORBED "
                        "into the replica (unlike --reconcile's frozen "
                        "file), and — with --gossip-peers — dial out on "
                        "a jittered timer so N such sidecars converge "
                        "from any divergence with no distinguished "
                        "source (see DESIGN.md gossip, ROBUSTNESS.md "
                        "convergence contract)")
    p.add_argument("--replica-key", default="replica", metavar="KEY",
                   help="this replica's name in gossip telemetry "
                        "(default: replica)")
    p.add_argument("--gossip-peers", default=None, metavar="HOST:PORT,...",
                   help="comma list of peer --replica sidecars to "
                        "gossip with (requires --replica)")
    p.add_argument("--gossip-interval", type=float, default=1.0,
                   metavar="SECONDS",
                   help="mean seconds between gossip dials (jittered "
                        "full-spread via BackoffPolicy; consecutive "
                        "all-peer failures back off; default: 1)")
    p.add_argument("--snapshot", metavar="DATAFILE", default=None,
                   help="snapshot bootstrap mode (ISSUE 12): materialize "
                        "DATAFILE once as content-addressed CDC chunks "
                        "and serve every connection as a snapshot "
                        "responder — a stale joiner reconciles its chunk "
                        "set first and moves O(diff) bytes, a cold one "
                        "streams the shared full-manifest log.  With "
                        "--fanout the protocol is served on its own "
                        "--snapshot-port and the structured "
                        "snapshot-needed record carries the redirect "
                        "hint (see WIRE.md Snapshot, DESIGN.md "
                        "bootstrap)")
    p.add_argument("--snapshot-port", type=int, default=0, metavar="PORT",
                   help="dedicated snapshot listener port for the "
                        "--fanout composition (default: 0 = ephemeral; "
                        "the bound port rides the snapshot-needed "
                        "hint)")
    p.add_argument("--snapshot-offset", type=int, default=0,
                   metavar="BYTES",
                   help="live-log wire offset the --snapshot dataset "
                        "materializes — where an assembled joiner "
                        "attaches its live session (default: 0)")
    p.add_argument("--max-retries", type=int, default=5, metavar="N",
                   help="transient-failure budget: bind/accept errors are "
                        "retried with backoff at most N times before the "
                        "daemon fails with a structured error (default: 5)")
    p.add_argument("--backoff-base", type=float, default=0.05,
                   metavar="SECONDS",
                   help="base of the exponential-backoff-with-full-jitter "
                        "retry delay: attempt k sleeps uniform(0, "
                        "min(cap, base * 2^k)) (default: 0.05)")
    p.add_argument("--stats-fd", type=int, default=None, metavar="FD",
                   help="enable telemetry and write one JSON metrics "
                        "snapshot line to this file descriptor every "
                        "--stats-interval seconds; SIGUSR1 forces an "
                        "immediate one-shot dump (see OBSERVABILITY.md)")
    p.add_argument("--stats-interval", type=float,
                   default=DEFAULT_STATS_INTERVAL, metavar="SECONDS",
                   help="period between --stats-fd snapshots "
                        f"(default: {DEFAULT_STATS_INTERVAL:.0f})")
    p.add_argument("--stats-format", choices=("json", "prom"),
                   default="json",
                   help="--stats-fd output format: self-contained JSON "
                        "lines (default) or Prometheus text exposition "
                        "blocks (obs.metrics.to_prom_text)")
    p.add_argument("--obs-http", type=int, default=None, metavar="PORT",
                   help="enable telemetry and serve the read-only scrape "
                        "endpoint on 127.0.0.1:PORT — /metrics (Prometheus "
                        "text), /snapshot (the --stats-fd JSON record), "
                        "/healthz (staged health, 503 when degraded), "
                        "/events (bounded JSONL tail); 0 binds an "
                        "ephemeral port (see OBSERVABILITY.md fleet plane)")
    p.add_argument("--flight-dir", metavar="DIR", default=None,
                   help="arm the flight recorder: on any protocol error "
                        "or retry exhaustion, dump an atomic post-mortem "
                        "bundle (event/span rings, metrics, checkpoint) "
                        "into DIR for offline attribution (enables "
                        "telemetry; see OBSERVABILITY.md)")
    p.add_argument("--trace-jsonl", metavar="PATH", default=None,
                   help="enable telemetry and mirror every event AND "
                        "wire-offset span as JSONL into PATH — the "
                        "per-peer log `python -m "
                        "dat_replication_protocol_tpu.obs timeline` "
                        "merges")
    args = p.parse_args(argv)
    drain = args.drain_timeout if args.drain_timeout > 0 else None
    from .session.reconnect import BackoffPolicy

    policy = BackoffPolicy(base=args.backoff_base,
                           max_retries=args.max_retries)
    emitter = None
    trace_sink = None
    if args.backend == "host":
        os.environ["DAT_DEVICE_HASH"] = "0"  # routing-layer override:
        # force the host digest engine for this daemon's lifetime
    if os.environ.get("DAT_DEVICE_HASH") != "0":
        # a device program is reachable: place the persistent compile
        # cache before the first one compiles (a cold sidecar compiles
        # one program per (batch, block-count) bucket it meets)
        from .utils.cache import enable_compile_cache

        enable_compile_cache()
    # resolve the digest engine NOW, before listening: a backend that
    # cannot initialise or a device engine that cannot import raises
    # out of main (non-zero exit, the cause on stderr) instead of a
    # daemon that answers from the host
    from .backend.tpu_backend import resolve_digest_engine

    device_rec, _ = resolve_digest_engine()
    if device_rec["platform"] is not None:
        # jax is in play: mirror its compile-cache and compile-seconds
        # accounting into the registry (dark unless telemetry is on)
        obs_device.watch_compile_events()
    if args.flight_dir:
        # arming enables telemetry: a dark ring has nothing to dump
        obs_flight.FLIGHT.arm(args.flight_dir)
    if args.trace_jsonl:
        obs_metrics.enable()
        trace_sink = obs_tracing.attach_jsonl_sink(args.trace_jsonl)
    if args.stats_fd is not None:
        # --stats-fd IS the telemetry opt-in; a snapshot reader needs
        # no per-frame instants (--trace-jsonl/--flight-dir light those)
        obs_metrics.enable(frames=False)
        emitter = StatsEmitter(args.stats_fd, args.stats_interval,
                               fmt=args.stats_format).start()
        _install_sigusr1(emitter)
    if args.snapshot and (args.hub or args.reconcile):
        p.error("--snapshot cannot combine with --hub/--reconcile "
                "(it composes with --fanout, where it answers the "
                "broadcast's snapshot-needed refusals)")
    if args.replica and (args.hub or args.fanout or args.reconcile
                         or args.snapshot):
        p.error("--replica is its own session mode; it cannot combine "
                "with --hub/--fanout/--reconcile/--snapshot")
    if args.replica and args.stdio:
        p.error("--replica gossips with many peers; it needs --tcp")
    if args.gossip_peers and not args.replica:
        p.error("--gossip-peers requires --replica")
    if args.edge and args.stdio:
        p.error("--edge is the event-driven TCP front; it needs --tcp")
    hub = None
    if args.edge and not args.hub and not (args.reconcile or args.replica
                                           or args.snapshot):
        # --edge implies --hub for session legs: the unified table's
        # hub sessions ride the shared engine's admission/window/shed
        # ladder — without a hub there is no stage to preserve
        args.hub = True
    if args.hub:
        if args.stdio:
            p.error("--hub multiplexes many connections; it needs --tcp")
        from .hub import ReplicationHub

        mesh = args.hub_mesh
        if mesh is not None and mesh != "auto":
            mesh = int(mesh)
        hub = ReplicationHub(mesh=mesh,
                             max_sessions=args.hub_max_sessions,
                             parked_budget=args.hub_parked_budget)
        set_active_hub(hub)
        if hub.mesh_devices:
            # the served batch engine laid over the host's chips (not
            # the private scan engine a mesh hub ran before ISSUE 35,
            # which called itself "mesh-sharded")
            device_rec = dict(device_rec, engine="device-batch-mesh",
                              mesh_devices=hub.mesh_devices)
    set_device_record(device_rec)
    print("sidecar: device " + " ".join(
        f"{k}={json.dumps(v)}" for k, v in device_rec.items()
        if v is not None), file=sys.stderr, flush=True)
    fanout = None
    if args.fanout:
        if args.stdio:
            p.error("--fanout broadcasts to many connections; it needs "
                    "--tcp")
        from .fanout import FanoutServer

        fanout = FanoutServer(
            retention_budget=args.fanout_retention,
            window_bytes=args.fanout_window,
            stall_timeout=args.fanout_stall_timeout)
        set_active_fanout(fanout)
    replica = None
    if args.reconcile:
        if args.hub or args.fanout:
            p.error("--reconcile is its own session mode; it cannot "
                    "combine with --hub/--fanout")
        replica = load_reconcile_replica(args.reconcile)
    replica_node = None
    gossip_driver = None
    if args.replica:
        replica_node = load_replica_node(args.replica, args.replica_key)
        if args.gossip_peers:
            from .cluster import GossipDriver

            gossip_driver = GossipDriver(
                replica_node,
                [p_.strip() for p_ in args.gossip_peers.split(",")],
                interval=args.gossip_interval).start()
            set_active_gossip(gossip_driver)
        else:
            set_active_gossip(replica_node)
    snapshot_source = None
    if args.snapshot:
        snapshot_source = load_snapshot_source(
            args.snapshot, wire_offset=args.snapshot_offset)
    obs_srv = None
    if args.obs_http is not None:
        # a dark endpoint would serve zeros; no route of it serves the
        # per-frame instants
        obs_metrics.enable(frames=False)
        obs_srv = obs_http.ObsHttpServer(
            args.obs_http, snapshot_fn=snapshot_stats,
            admission_fn=_active_admission_fn()).start()
        print(f"sidecar: obs endpoint on {obs_srv.url}",
              file=sys.stderr, flush=True)
    snap_listener = None
    try:
        if args.stdio:
            if snapshot_source is not None:
                from .session.transport import once

                def _swap_stdout_snap() -> None:
                    devnull = os.open(os.devnull, os.O_WRONLY)
                    os.dup2(devnull, 1)
                    os.close(devnull)

                stats = run_snapshot_session(
                    lambda n: os.read(0, n),
                    lambda d: _write_all(1, d),
                    once(_swap_stdout_snap), snapshot_source,
                    peer="stdio")
                print(f"sidecar: stdio session {stats}", file=sys.stderr,
                      flush=True)
                return 0 if stats["ok"] else 1
            if replica is not None:
                from .session.transport import once

                def _swap_stdout() -> None:
                    devnull = os.open(os.devnull, os.O_WRONLY)
                    os.dup2(devnull, 1)
                    os.close(devnull)

                stats = run_reconcile_session(
                    lambda n: os.read(0, n),
                    lambda d: _write_all(1, d),
                    once(_swap_stdout), replica, peer="stdio")
                print(f"sidecar: stdio session {stats}", file=sys.stderr,
                      flush=True)
                return 0 if stats["ok"] else 1
            stats = serve_stdio(drain_timeout=drain)
            return 0 if stats["ok"] else 1
        host, _, port = args.tcp.rpartition(":")
        host = host or "127.0.0.1"
        if fanout is not None and snapshot_source is not None:
            # the composition (ISSUE 12): snapshot sessions get their
            # own port; the broadcast's snapshot-needed refusals carry
            # the redirect hint to it
            from .wire.framing import CAP_SNAPSHOT

            snap_listener = SnapshotListener(
                snapshot_source, host, args.snapshot_port)
            fanout.snapshot_hint = {"port": snap_listener.port,
                                    "cap": CAP_SNAPSHOT}
            print(f"sidecar: snapshot bootstrap on "
                  f"{host}:{snap_listener.port}",
                  file=sys.stderr, flush=True)
            snapshot_source = None  # the main loop keeps broadcasting
        if args.edge:
            from .edge import EdgeLoop

            edge_loop = EdgeLoop(
                hub, fanouts={"main": fanout} if fanout else None,
                reconcile_replica=replica,
                snapshot_source=snapshot_source,
                replica_node=replica_node, drain_timeout=drain,
                # a stable per-process loop label: the fleet joins
                # edge.loop.lag{loop=} across targets by this name
                name=f"edge:{host}:{int(port)}")
            set_active_edge(edge_loop)
            try:
                edge_loop.bind(host, int(port))
                edge_loop.serve()
            finally:
                set_active_edge(None)
            return 0
        serve_tcp(host, int(port), drain_timeout=drain,
                  retry_policy=policy, hub=hub, fanout=fanout,
                  reconcile_replica=replica,
                  snapshot_source=snapshot_source,
                  replica_node=replica_node)
        return 0
    finally:
        if gossip_driver is not None:
            gossip_driver.close()
        if replica_node is not None:
            set_active_gossip(None)
        if snap_listener is not None:
            snap_listener.close()
        if obs_srv is not None:
            obs_srv.close()
        if fanout is not None:
            set_active_fanout(None)
            fanout.close()
        if hub is not None:
            set_active_hub(None)
            hub.close()
        if emitter is not None and emitter.stop():
            # final snapshot — ONLY once the periodic thread really
            # exited: two concurrent writers on one fd can interleave
            # past PIPE_BUF and corrupt the one-JSON-object-per-line
            # contract (an emitter still blocked on a never-drained
            # pipe keeps sole ownership of the fd instead)
            emitter.dump_once()
        if trace_sink is not None:
            obs_events.EVENTS.detach_sink()
            obs_tracing.SPANS.detach_sink()
            trace_sink.close()


if __name__ == "__main__":
    sys.exit(main())
