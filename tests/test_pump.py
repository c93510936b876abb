"""Unit layer for the kernel-bypass wire pump (ISSUE 14).

Syscall-batch edge cases the C loops must survive: partial sendmmsg
acceptance, EAGAIN mid-batch, fd death mid-loop, zero-length and
single-byte frames straddling receive batches, pipes (no mmsg support)
— plus the route selector and the fan-out gather's zero-Python-bytes
counter proof.  The byte-identical chaos sweep lives in
tests/test_pump_parity.py.
"""

from __future__ import annotations

import os
import socket
import threading
import time

import numpy as np
import pytest

import dat_replication_protocol_tpu as protocol
from dat_replication_protocol_tpu.obs import metrics as obs_metrics
from dat_replication_protocol_tpu.runtime import native
from dat_replication_protocol_tpu.session import pump
from dat_replication_protocol_tpu.session.decoder import Decoder
from dat_replication_protocol_tpu.wire.framing import TYPE_BLOB, frame

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable")


def _recv_all(sock: socket.socket) -> bytes:
    parts = []
    while True:
        d = sock.recv(1 << 16)
        if not d:
            return b"".join(parts)
        parts.append(d)


def _gather_for(payloads):
    g = pump.SpanGather()
    n = g.fill([memoryview(p) for p in payloads])
    return g, n


# -- probe / route selector ---------------------------------------------------


def test_probe_reports_syscall_tier():
    caps = pump.probe_caps()
    assert caps["native_available"] is True
    assert caps["route"] in ("native", "python")
    assert isinstance(caps["recvmmsg"], bool)
    assert isinstance(caps["sendmmsg"], bool)


def test_route_selector_resolution(monkeypatch):
    monkeypatch.setenv("DAT_PUMP", "python")
    assert pump.effective_pump_route() == "python"
    monkeypatch.setenv("DAT_PUMP", "native")
    assert pump.effective_pump_route() == "native"
    # unrecognized values resolve to the default (native when the
    # library loads — the DAT_CDC_ROUTE doctrine)
    monkeypatch.setenv("DAT_PUMP", "iouring")
    assert pump.effective_pump_route() == "native"
    monkeypatch.delenv("DAT_PUMP")
    assert pump.effective_pump_route() == "native"
    # no native library = no native route, whatever the env asks
    monkeypatch.setenv("DAT_NATIVE_DISABLE", "1")
    monkeypatch.setenv("DAT_PUMP", "native")
    assert pump.effective_pump_route() == "python"


# -- batched receive ----------------------------------------------------------


def test_recv_scan_batches_and_indexes(monkeypatch):
    monkeypatch.setenv("DAT_PUMP", "native")
    a, b = socket.socketpair()
    try:
        wire = frame(TYPE_BLOB, b"x" * 1000) * 40
        a.sendall(wire)
        a.shutdown(socket.SHUT_WR)
        dec = Decoder()
        got = []
        dec.blob(lambda blob, done: blob.collect(
            lambda data: (got.append(data), done())))
        pump.recv_pump(dec, b.fileno())
        assert dec.finished and len(got) == 40
        assert all(g == b"x" * 1000 for g in got)
    finally:
        a.close()
        b.close()


def test_zero_length_and_single_byte_frames_straddle_batches(monkeypatch):
    """A zero-length blob frame (flen=1: id only) and frames whose
    headers arrive ONE BYTE PER PUMP BATCH must decode exactly like a
    whole-buffer write — batch boundaries are not frame boundaries."""
    monkeypatch.setenv("DAT_PUMP", "native")
    wire = (frame(TYPE_BLOB, b"") + frame(TYPE_BLOB, b"z")
            + frame(TYPE_BLOB, b"") + frame(TYPE_BLOB, b"tail"))
    a, b = socket.socketpair()
    try:
        dec = Decoder()
        got = []
        dec.blob(lambda blob, done: blob.collect(
            lambda data: (got.append(data), done())))

        def feed():
            # one byte per send, paced so most land in separate pump
            # batches (the blocking first read takes whatever is there)
            for i in range(len(wire)):
                a.sendall(wire[i:i + 1])
                if i % 3 == 0:
                    time.sleep(0.002)
            a.shutdown(socket.SHUT_WR)

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        pump.recv_pump(dec, b.fileno())
        t.join(10)
        assert dec.finished
        assert got == [b"", b"z", b"", b"tail"]
        assert dec.blobs == 4
    finally:
        a.close()
        b.close()


def test_recv_pump_on_pipe_degrades_to_plain_reads(monkeypatch):
    """Pipes have no recvmmsg (ENOTSOCK): the pump's wakeup read must
    carry the session alone — the sidecar --stdio shape."""
    monkeypatch.setenv("DAT_PUMP", "native")
    r, w = os.pipe()
    try:
        wire = frame(TYPE_BLOB, b"p" * 500) * 8
        os.write(w, wire)
        os.close(w)
        w = None
        dec = Decoder()
        got = []
        dec.blob(lambda blob, done: blob.collect(
            lambda data: (got.append(data), done())))
        pump.recv_pump(dec, r)
        assert dec.finished and len(got) == 8
    finally:
        os.close(r)
        if w is not None:
            os.close(w)


def test_write_indexed_falls_back_mid_frame():
    """The bulk entry only installs at a clean boundary; mid-frame it
    must route through write() with identical results."""
    wire = frame(TYPE_BLOB, b"A" * 1000)
    dec = Decoder()
    got = []
    dec.blob(lambda blob, done: blob.collect(
        lambda data: (got.append(data), done())))
    dec.write(wire[:100])  # now mid-blob
    starts = np.zeros(4, np.int64)
    lens = np.zeros(4, np.int64)
    ids = np.zeros(4, np.uint8)
    # a (bogus) index must be ignored: the parser is mid-frame
    ok = dec.write_indexed(wire[100:], starts, lens, ids, 1, 50)
    assert ok
    dec.end()
    assert got == [b"A" * 1000]


# -- gather send --------------------------------------------------------------


def test_send_spans_blocking_gather_exact_bytes():
    payloads = [os.urandom(137) for _ in range(300)]
    g, n = _gather_for(payloads)
    a, b = socket.socketpair()
    try:
        got = {}
        t = threading.Thread(target=lambda: got.__setitem__("d", _recv_all(b)),
                             daemon=True)
        t.start()
        w = native.pump_send_spans(a.fileno(), g.addrs, g.lens, n, g.stats)
        a.shutdown(socket.SHUT_WR)
        t.join(10)
        assert w == sum(len(p) for p in payloads)
        assert got["d"] == b"".join(payloads)
        # the whole 300-span batch cost far fewer kernel entries
        assert int(g.stats[0]) < 300
    finally:
        g.release()
        a.close()
        b.close()


def test_send_spans_nb_eagain_mid_batch_returns_accepted():
    """A non-blocking fd that stops accepting mid-batch must return the
    accepted byte count (no exception, no spin) — the fan-out window
    bookkeeping contract."""
    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
        a.setblocking(False)
        payloads = [b"q" * 4096 for _ in range(200)]  # >> the send buffer
        g, n = _gather_for(payloads)
        accepted = pump.send_spans_nb(a.fileno(), g, n)
        g.release()
        assert 0 < accepted < sum(len(p) for p in payloads)
        # drain and finish: partial acceptance resumes exactly at the
        # accepted offset (receiver sees one contiguous stream)
        whole = b"".join(payloads)
        got = []
        sent = accepted
        b.setblocking(False)
        deadline = time.monotonic() + 30
        while (sent < len(whole) or len(b"".join(got)) < len(whole)) \
                and time.monotonic() < deadline:
            try:
                got.append(b.recv(1 << 16))
            except BlockingIOError:
                pass
            if sent < len(whole):
                g2, n2 = _gather_for([whole[sent:]])
                sent += pump.send_spans_nb(a.fileno(), g2, n2)
                g2.release()
        assert b"".join(got) == whole
    finally:
        a.close()
        b.close()


def test_send_to_dead_fd_raises_oserror():
    a, b = socket.socketpair()
    a_fd = os.dup(a.fileno())
    a.close()
    b.close()
    os.close(a_fd)  # fd is gone: the pump must surface EBADF, not hang
    g, n = _gather_for([b"x" * 100])
    with pytest.raises(OSError):
        pump.send_spans_nb(a_fd, g, n)
    g.release()


def test_send_pump_partial_writes_resume(monkeypatch):
    """Blocking gather against a slow reader: partial kernel accepts
    resume mid-span natively; every byte arrives in order."""
    monkeypatch.setenv("DAT_PUMP", "native")
    enc = protocol.encode()
    blob = enc.blob(2 << 20)
    blob.write(os.urandom(2 << 20))
    blob.end()
    enc.finalize()
    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 32768)
        got = {}

        def slow_reader():
            parts = []
            while True:
                d = b.recv(8192)
                if not d:
                    break
                parts.append(d)
                time.sleep(0.0002)
            got["d"] = b"".join(parts)

        t = threading.Thread(target=slow_reader, daemon=True)
        t.start()
        pump.send_pump(enc, a.fileno(),
                       close=lambda: a.shutdown(socket.SHUT_WR))
        t.join(30)
        from dat_replication_protocol_tpu.wire.framing import frame_wire_len

        assert len(got["d"]) == frame_wire_len(2 << 20)
    finally:
        a.close()
        b.close()


# -- pump_reader / pump_writer drop-ins --------------------------------------


def test_pump_io_roundtrip(monkeypatch):
    monkeypatch.setenv("DAT_PUMP", "native")
    a, b = socket.socketpair()
    try:
        wr = pump.pump_writer(a.fileno())
        rd = pump.pump_reader(b.fileno())
        payload = os.urandom(300_000)
        t = threading.Thread(
            target=lambda: (wr(payload), a.shutdown(socket.SHUT_WR)),
            daemon=True)
        t.start()
        parts = []
        while True:
            d = rd(65536)
            if not d:
                break
            parts.append(d)
        t.join(10)
        assert b"".join(parts) == payload
    finally:
        a.close()
        b.close()


# -- fan-out gather: zero Python-owned payload bytes --------------------------


def test_fanout_native_gather_counter_proof(monkeypatch, obs_enabled):
    """On the native route every delivered broadcast byte rides the
    native gather (transport.pump.gather.bytes == fanout.sent.bytes):
    payload bytes go kernel-ward as (address, length) spans over
    BroadcastLog segment memory — no Python-owned copies on the hot
    path — while digest work stays zero however many peers attach
    (the hash-once economics are the source session's, untouched)."""
    from dat_replication_protocol_tpu.fanout import FanoutServer

    monkeypatch.setenv("DAT_PUMP", "native")
    srv = FanoutServer(max_peers=8, window_bytes=1 << 22)
    socks = []
    peers = []
    try:
        assert srv._gather is not None  # the route resolved native
        got = {}
        readers = []
        for i in range(4):
            a, b = socket.socketpair()
            socks.append((a, b))
            peers.append(srv.attach_peer(f"p{i}", fd=a.fileno(), offset=0))
            t = threading.Thread(
                target=lambda i=i, b=b: got.__setitem__(i, _recv_all(b)),
                daemon=True)
            t.start()
            readers.append(t)
        payload = os.urandom(1 << 20)
        srv.publish(payload)
        srv.seal()
        assert srv.drain(timeout=30)
        for i, (a, b) in enumerate(socks):
            peers[i].close()
            a.close()
        # the server's owned fd dups close with it; readers then see EOF
        srv.close()
        for t in readers:
            t.join(10)
        assert all(got.get(i) == payload for i in range(4))
        snap = obs_metrics.snapshot()["counters"]
        assert snap["fanout.sent.bytes"] == 4 * len(payload)
        assert snap["transport.pump.gather.bytes"] == 4 * len(payload)
        assert snap["device.native.hash.bytes"] == 0  # hash-once: zero here
    finally:
        srv.close()
        for a, b in socks:
            a.close()
            b.close()


def test_fanout_python_route_unchanged(monkeypatch):
    """DAT_PUMP=python pins the os.writev path (the server resolves at
    construction): same bytes, gather counter dark."""
    from dat_replication_protocol_tpu.fanout import FanoutServer

    monkeypatch.setenv("DAT_PUMP", "python")
    srv = FanoutServer(max_peers=4)
    a, b = socket.socketpair()
    try:
        assert srv._gather is None
        peer = srv.attach_peer("p0", fd=a.fileno(), offset=0)
        payload = os.urandom(100_000)  # fits the kernel buffer whole
        srv.publish(payload)
        srv.seal()
        assert srv.drain(timeout=30)
        peer.close()
        a.close()
        srv.close()  # releases the owned fd dup -> reader sees EOF
        assert _recv_all(b) == payload
    finally:
        srv.close()
        a.close()
        b.close()


# -- sidecar route surfacing --------------------------------------------------


def test_stats_snapshot_carries_pump_route(monkeypatch):
    from dat_replication_protocol_tpu import sidecar

    monkeypatch.setenv("DAT_PUMP", "native")
    snap = sidecar.snapshot_stats()
    assert snap["pump"]["route"] == "native"
    assert snap["pump"]["native_available"] is True
    monkeypatch.setenv("DAT_PUMP", "python")
    assert sidecar.snapshot_stats()["pump"]["route"] == "python"


def test_hub_snapshot_carries_pump_route(monkeypatch):
    from dat_replication_protocol_tpu.hub import ReplicationHub

    monkeypatch.setenv("DAT_PUMP", "python")
    hub = ReplicationHub(max_sessions=2)
    try:
        assert hub.snapshot()["pump_route"] == "python"
    finally:
        hub.close()


# -- the edge turn's two halves (ISSUE 36) -----------------------------------


def _changes_wire(lo: int, hi: int) -> bytes:
    enc = protocol.encode()
    for i in range(lo, hi):
        enc.change({"key": f"k{i}", "change": i, "from": 0, "to": 1,
                    "value": b"v" * (i * 7)})
    parts = []
    while True:
        d = enc.read(1 << 20)
        if not d:
            return b"".join(parts)
        parts.append(bytes(d))


def _mixed_wire() -> tuple:
    """Changes, zero-length blobs (raw frames: the encoder refuses
    them), blobs wider than a receive slab, changes again:
    ``(wire, blobs, n_changes)``."""
    blobs = [b"", b"\x01" * 70_000, b"", bytes(range(256)) * 900, b"tail"]
    wire = (_changes_wire(0, 40)
            + b"".join(frame(TYPE_BLOB, data) for data in blobs)
            + _changes_wire(40, 60))
    return wire, blobs, 60


def _drain_one(how: str, ep, dec, fan) -> tuple:
    if how == "step":
        return pump.recv_step(ep, dec)
    if how == "halves":
        return pump.recv_feed(ep, dec, pump.recv_fetch(ep))
    fan.start("tok", ep)
    token, fetched = fan.wait_one()
    assert token == "tok"
    return pump.recv_feed(ep, dec, fetched)


@pytest.mark.parametrize("how", ["step", "halves", "fan"])
def test_recv_step_is_fetch_then_feed(monkeypatch, how):
    """recv_step, its two halves called in a row, and the halves with
    the receive on a helper thread deliver the same frames in the same
    order from the same bytes — slabs far smaller than the blobs, so
    every blob straddles several."""
    monkeypatch.setenv("DAT_PUMP", "native")
    wire, blobs, n_changes = _mixed_wire()
    a, b = socket.socketpair()
    b.setblocking(False)
    fan = pump.RecvFan(2) if how == "fan" else None
    try:
        dec = Decoder()
        got: list = []
        dec.change(lambda ch, done: (got.append(("c", ch.key)), done()))
        dec.blob(lambda blob, done: blob.collect(
            lambda data: (got.append(("b", bytes(data))), done())))
        ep = pump.EdgePump(b.fileno(), cap=16 << 10)
        sender = threading.Thread(
            target=lambda: (a.sendall(wire), a.shutdown(socket.SHUT_WR)),
            daemon=True)
        sender.start()
        total, eof = 0, False
        deadline = time.monotonic() + 20
        while not eof and time.monotonic() < deadline:
            n, eof = _drain_one(how, ep, dec, fan)
            total += n
        sender.join(10)
        assert eof and total == len(wire)
        dec.end()
        assert dec.finished
        assert [g for g in got if g[0] == "b"] == [("b", d) for d in blobs]
        assert [g[1] for g in got if g[0] == "c"] == [
            f"k{i}" for i in range(n_changes)]
        # changes 0-39 before the blobs, 40-59 after: one stream order
        assert got.index(("b", b"tail")) < got.index(("c", "k40"))
    finally:
        if fan is not None:
            fan.close()
        a.close()
        b.close()


def test_recv_feed_observes_bulk_and_tells_errors_apart(monkeypatch):
    """The feed half is where a helper's raw result becomes the turn's
    outcome: would-block, EOF, a transport error raised on the feeding
    thread, a vanished library — and `bulk` says whether the receive
    came back with a full slice."""
    monkeypatch.setenv("DAT_PUMP", "native")
    monkeypatch.setattr(pump, "PUMP_SLICE", 4096)
    a, b = socket.socketpair()
    b.setblocking(False)
    try:
        dec = Decoder()
        got = []
        dec.blob(lambda blob, done: blob.collect(
            lambda data: (got.append(len(data)), done())))
        ep = pump.EdgePump(b.fileno(), cap=1 << 16)
        assert ep.bulk is False
        assert pump.recv_feed(ep, dec, pump.recv_fetch(ep)) == (0, False)
        a.sendall(frame(TYPE_BLOB, b"x" * 100))
        n, eof = pump.recv_feed(ep, dec, pump.recv_fetch(ep))
        assert n > 100 and not eof and ep.bulk is False and got == [100]
        a.sendall(frame(TYPE_BLOB, b"y" * 20_000))
        n, eof = pump.recv_feed(ep, dec, pump.recv_fetch(ep))
        assert n > 20_000 and ep.bulk is True and got == [100, 20_000]
        buf = np.empty(16, dtype=np.uint8)
        with pytest.raises(OSError) as ei:
            pump.recv_feed(ep, dec, (buf, (-104, 0, 0, 0), 0.0))
        assert ei.value.errno == 104 and ep.bulk is False
        assert pump.recv_feed(ep, dec, (buf, (0, 0, 0, 0), 0.0)) == (0, True)
        # the library gone between the two halves: the python arm, and
        # never bulk again
        ep.bulk = True
        a.sendall(frame(TYPE_BLOB, b"z" * 7))
        n, eof = pump.recv_feed(ep, dec, (buf, None, 0.0))
        assert n > 7 and ep.native is False and ep.bulk is False
        assert got == [100, 20_000, 7]
    finally:
        a.close()
        b.close()


def test_recv_fan_hands_back_what_a_helper_raised_and_closes():
    fan = pump.RecvFan(2)
    names = {t.name for t in threading.enumerate()}
    assert {"edge-rx-0", "edge-rx-1"} <= names
    fan.start("bad", object())  # no recv_st: AttributeError on the helper
    token, fetched = fan.wait_one()
    assert token == "bad" and isinstance(fetched, AttributeError)
    a, b = socket.socketpair()
    try:
        b.setblocking(False)
        ep = pump.EdgePump(b.fileno())
        b_fd = b.fileno()
        b.close()  # the descriptor dies: an errno, not an exception
        fan.start("dead", ep)
        token, (buf, r, seconds) = fan.wait_one()
        assert token == "dead" and r[0] == -9 and b_fd == ep.fd  # EBADF
    finally:
        a.close()
    fan.close()
    assert not any(t.name.startswith("edge-rx-")
                   for t in threading.enumerate())


def test_recv_fan_stress_keeps_every_stream_whole(monkeypatch):
    """Sixteen streams over four helpers under a short switch interval:
    the per-session state a helper writes (slab, index arrays, stats)
    never leaks into a neighbour's feed — every decoder gets its own
    blobs, whole and in order."""
    import sys

    monkeypatch.setenv("DAT_PUMP", "native")
    n = 16
    wires, wants = [], []
    for i in range(n):
        blobs = [bytes([i, j]) * (3_000 + 997 * ((i + j) % 7))
                 for j in range(40)]
        wants.append(blobs)
        wires.append(b"".join(frame(TYPE_BLOB, b) for b in blobs))
    pairs = [socket.socketpair() for _ in range(n)]
    fan = pump.RecvFan(4)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        gots, decs, eps = [], [], []
        for a, b in pairs:
            b.setblocking(False)
            got: list = []
            dec = Decoder()
            dec.blob(lambda blob, done, got=got: blob.collect(
                lambda data: (got.append(bytes(data)), done())))
            gots.append(got)
            decs.append(dec)
            eps.append(pump.EdgePump(b.fileno(), cap=8 << 10))
        senders = [threading.Thread(
            target=lambda a=a, w=w: (a.sendall(w),
                                     a.shutdown(socket.SHUT_WR)),
            daemon=True) for (a, _b), w in zip(pairs, wires)]
        for t in senders:
            t.start()
        live = set(range(n))
        deadline = time.monotonic() + 30
        while live and time.monotonic() < deadline:
            for i in live:
                fan.start(i, eps[i])
            for _ in range(len(live)):
                i, fetched = fan.wait_one()
                _nbytes, eof = pump.recv_feed(eps[i], decs[i], fetched)
                if eof:
                    live.discard(i)
        assert not live, "streams still open at the deadline"
        for t in senders:
            t.join(10)
            assert not t.is_alive()
        for i in range(n):
            decs[i].end()
            assert decs[i].finished and gots[i] == wants[i], f"stream {i}"
    finally:
        sys.setswitchinterval(was)
        fan.close()
        for a, b in pairs:
            a.close()
            b.close()


# -- the thread-per-connection read-ahead (ISSUE 39) --------------------------
#
# Slices and slabs are shrunk so that a socketpair fills whole slices and
# the helper engages within a few receives; the rules are the same at any
# size.  RA_SLICE / RA_CAP: what a "full receive" is, and a slab.

RA_SLICE = 16 << 10
RA_CAP = 64 << 10


def _h(data) -> bytes:
    import hashlib

    return hashlib.blake2b(bytes(data), digest_size=32).digest()


def _big_blob(i: int) -> bytes:
    return bytes((b + 37 * i) & 0xFF for b in range(256)) * 4096  # 1 MiB


def _ra_wire(traffic: str) -> tuple:
    """``(wire, expected deliveries)``: ``("c", key)`` a change,
    ``("b", digest)`` a blob."""
    if traffic == "blobs":
        blobs = [_big_blob(i) for i in range(5)]
        return (b"".join(frame(TYPE_BLOB, b) for b in blobs),
                [("b", _h(b)) for b in blobs])
    if traffic == "changes":
        return (_changes_wire(0, 3000),
                [("c", f"k{i}") for i in range(3000)])
    wire, blobs, n = _mixed_wire()
    big = _big_blob(9)
    want = ([("c", f"k{i}") for i in range(40)]
            + [("b", _h(b)) for b in blobs]
            + [("c", f"k{i}") for i in range(40, n)] + [("b", _h(big))])
    return wire + frame(TYPE_BLOB, big), want


def _rows_wire(lo: int, hi: int) -> bytes:
    """Change rows of one size (~100 bytes on the wire)."""
    enc = protocol.encode()
    for i in range(lo, hi):
        enc.change({"key": f"k{i}", "change": i, "from": 0, "to": 1,
                    "value": b"r" * 80})
    return bytes(enc.read(1 << 30))


def _ra_decoder(got: list) -> Decoder:
    dec = Decoder()
    dec.change(lambda ch, done: (got.append(("c", ch.key)), done()))
    dec.blob(lambda blob, done: blob.collect(
        lambda data: (got.append(("b", _h(data))), done())))
    return dec


def _ra_setup(monkeypatch, depth: int) -> list:
    """Pin the native route, shrink the slice and set the depth (0: the
    slices never fill, so the connection stays inline); returns the
    list every helper start is recorded in."""
    monkeypatch.setenv("DAT_PUMP", "native")
    monkeypatch.setattr(pump, "PUMP_SLICE", RA_SLICE if depth else RA_CAP * 2)
    monkeypatch.setattr(pump, "READAHEAD", max(depth, 1))
    made = []
    real = pump.RecvFan

    def fan(*a, **k):
        made.append(k.get("name"))
        return real(*a, **k)

    monkeypatch.setattr(pump, "RecvFan", fan)
    return made


def _send_quietly(sock: socket.socket, data: bytes) -> None:
    try:
        sock.sendall(data)
    except OSError:
        pass  # the test closed the pair under a blocked send


def _helpers_alive() -> list:
    return [t.name for t in threading.enumerate()
            if t.name.startswith("pump-rx")]


@pytest.mark.parametrize("depth", [0, 1, 2], ids=["inline", "d1", "d2"])
@pytest.mark.parametrize("traffic", ["blobs", "changes", "mix"])
def test_read_ahead_delivers_what_the_inline_pump_does(monkeypatch, traffic,
                                                       depth):
    """The same frames in the same order, the same digests and the
    same tap bytes, whether the receives run in a row with the feeds or
    on the helper up to ``depth`` slabs ahead of them — 1 MiB blobs
    across many slabs, change rows, and the two mixed."""
    made = _ra_setup(monkeypatch, depth)
    wire, want = _ra_wire(traffic)
    a, b = socket.socketpair()
    tapped: list = []
    got: list = []
    try:
        dec = _ra_decoder(got)
        sender = threading.Thread(
            target=lambda: (a.sendall(wire), a.shutdown(socket.SHUT_WR)),
            daemon=True)
        sender.start()
        pump.recv_pump(dec, b.fileno(), tap=lambda v: tapped.append(bytes(v)),
                       cap=RA_CAP)
        sender.join(10)
        assert dec.finished and dec.bytes == len(wire)
    finally:
        a.close()
        b.close()
    assert got == want
    assert b"".join(tapped) == wire
    assert made == (["pump-rx"] if depth else [])
    assert not _helpers_alive()


def test_read_ahead_counts_its_slabs_and_waits_lit(monkeypatch, obs_enabled):
    """Lit: every slab taken from the helper is counted, the ones that
    were in already as `ready`; the session thread's waits for it and
    the helper's receives are stage spans of their own."""
    _ra_setup(monkeypatch, 1)
    wire, want = _ra_wire("blobs")
    a, b = socket.socketpair()
    got: list = []
    try:
        dec = _ra_decoder(got)
        threading.Thread(target=lambda: (a.sendall(wire),
                                         a.shutdown(socket.SHUT_WR)),
                         daemon=True).start()
        pump.recv_pump(dec, b.fileno(), cap=RA_CAP)
    finally:
        a.close()
        b.close()
    assert got == want
    snap = obs_metrics.snapshot()
    c, h = snap["counters"], snap["histograms"]
    slabs = c["pump.readahead.slabs"]
    assert slabs > 0 and 0 <= c["pump.readahead.ready"] <= slabs
    # every slab was received under pump.recv, inline or on the helper
    # (the EOF's receive too), and every one was fed but the EOF
    assert h["span.pump.recv.seconds"]["count"] >= slabs + 1
    assert h["span.decode.write.seconds"]["count"] == \
        c["transport.pump.batches"]
    assert h["span.pump.wait.seconds"]["count"] <= slabs


def _counted_halves(monkeypatch) -> tuple:
    """Record the pump of every receive that brought bytes, and of
    every feed, in order."""
    fetched, fed = [], []
    real_fetch, real_feed = pump.recv_fetch, pump.recv_feed

    def fetch(p):
        out = real_fetch(p)
        if out[1] is not None and out[1][0] > 0:
            fetched.append(p)
        return out

    def feed(p, dec, got, tap=None):
        fed.append(p)
        return real_feed(p, dec, got, tap)

    monkeypatch.setattr(pump, "recv_fetch", fetch)
    monkeypatch.setattr(pump, "recv_feed", feed)
    return fetched, fed


def _holding_decoder(hold_key: str) -> tuple:
    """A decoder whose change handler never acks ``hold_key`` until the
    test calls the held ``done``."""
    held, keys = [], []

    def on_change(ch, done):
        keys.append(ch.key)
        if ch.key == hold_key and not held:
            held.append(done)
        else:
            done()

    dec = Decoder()
    dec.change(on_change)
    return dec, held, keys


def _settled(read, quiet: float = 0.3, timeout: float = 10.0):
    """``read()`` once it has returned the same value for ``quiet``
    seconds (or at ``timeout``)."""
    last, since = read(), time.monotonic()
    deadline = since + timeout
    while time.monotonic() < deadline:
        time.sleep(0.02)
        now = read()
        if now != last:
            last, since = now, time.monotonic()
        elif time.monotonic() - since >= quiet:
            break
    return last


def _wait_for(pred, timeout: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout
    while not pred() and time.monotonic() < deadline:
        time.sleep(0.01)
    return pred()


@pytest.mark.parametrize("depth", [1, 2])
def test_read_ahead_stops_at_its_depth_past_a_stalled_decoder(monkeypatch,
                                                              depth):
    """A handler that never acks stalls the decoder: the helper then
    starts no receive, so at most ``depth`` slabs are received past
    the one that stalled (``depth`` x the slab size of socket the
    pump takes beyond an inline pump's).  On the ack the stream
    resumes whole."""
    _ra_setup(monkeypatch, depth)
    fetched, fed = _counted_halves(monkeypatch)
    wire = _rows_wire(0, 6000)
    dec, held, keys = _holding_decoder("k4000")
    a, b = socket.socketpair()
    t = threading.Thread(target=pump.recv_pump,
                         args=(dec, b.fileno()), kwargs={"cap": RA_CAP},
                         daemon=True)
    threading.Thread(
        target=lambda: (a.sendall(wire), a.shutdown(socket.SHUT_WR)),
        daemon=True).start()
    t.start()
    try:
        assert _wait_for(lambda: held), "the stall never came"
        # what the helper may still receive, it has once the two
        # counts hold still
        stalled_at, got = _settled(lambda: (len(fed), len(fetched)))
        assert got == stalled_at + depth
    finally:
        if held:
            held[0]()
        t.join(10)
        a.close()
        b.close()
    assert not t.is_alive()
    assert dec.finished and keys == [f"k{i}" for i in range(6000)]
    assert not _helpers_alive()


@pytest.mark.parametrize("depth", [1, 2])
def test_a_slab_read_ahead_leaves_the_parked_index_alone(monkeypatch, depth):
    """Rule 3: the decoder parks on a slab's bulk cursor, whose index
    arrays are views of that slab's ``_RecvState``; the slabs the helper
    receives meanwhile land in index arrays of their own, so the parked
    index reads the same before and after, and shares no memory with
    them.  The peer sends whole frames a slab at a time, so that every
    slab installs its own index (``Decoder.write_indexed``)."""
    _ra_setup(monkeypatch, depth)
    fetched, fed = _counted_halves(monkeypatch)
    chunks = [_rows_wire(400 * k, 400 * k + 400) for k in range(8)]
    assert all(RA_SLICE <= len(c) <= RA_CAP for c in chunks)
    dec, held, keys = _holding_decoder("k1400")  # mid-chunk 3
    a, b = socket.socketpair()

    def paced():
        for k, c in enumerate(chunks):
            a.sendall(c)
            if not _wait_for(lambda: len(fetched) > k, 5.0):
                _wait_for(lambda: not held or len(fetched) > k, 20.0)
        a.shutdown(socket.SHUT_WR)

    t = threading.Thread(target=pump.recv_pump,
                         args=(dec, b.fileno()), kwargs={"cap": RA_CAP},
                         daemon=True)
    threading.Thread(target=paced, daemon=True).start()
    t.start()
    try:
        assert _wait_for(lambda: held), "the stall never came"
        bulk = dec._bulk
        assert bulk is not None and fed[-1] is fetched[3]
        # the hazard is real: the parked index IS the slab's state
        assert np.shares_memory(bulk["starts_np"], fed[-1].recv_st.starts)
        index = {k: bulk[k].copy() for k in ("starts_np", "lens_np",
                                              "ids_np")}
        assert _wait_for(lambda: len(fetched) == 4 + depth)
        ahead = fetched[4:]
        time.sleep(0.2)
        assert dec._bulk is bulk and len(fed) == 4
        for k, was in index.items():
            assert np.array_equal(bulk[k], was)
        for p in ahead:
            assert not np.shares_memory(p.recv_st.starts, bulk["starts_np"])
    finally:
        if held:
            held[0]()
        t.join(10)
        a.close()
        b.close()
    assert not t.is_alive()
    assert dec.finished and keys == [f"k{i}" for i in range(3200)]


@pytest.mark.parametrize("fault", ["destroy", "shed", "transport-error"])
def test_read_ahead_teardown_joins_the_helper_and_keeps_the_fd(monkeypatch,
                                                               fault):
    """The three ways out of a connection that is not its EOF: the
    decoder destroyed from another thread while the helper sits in a
    receive on a silent peer, a SessionShed raised out of a feed, an
    errno from the helper's receive.  recv_pump returns (or raises the
    error, in slab order) within a bound, with no helper left behind
    and the descriptor still open: the close is the caller's."""
    from dat_replication_protocol_tpu.hub import SessionShed

    _ra_setup(monkeypatch, 1)
    blobs = [bytes([i]) * 100_000 for i in range(12)]
    wire = b"".join(frame(TYPE_BLOB, x) for x in blobs)
    fetched = []
    real_fetch = pump.recv_fetch

    def fetch(p):
        out = real_fetch(p)
        if (fault == "transport-error" and len(fetched) == 6
                and threading.current_thread().name.startswith("pump-rx")):
            out = (out[0], (-104, 0, 0, 0), out[2])  # ECONNRESET
        fetched.append(out[1][0])
        return out

    monkeypatch.setattr(pump, "recv_fetch", fetch)
    got = []

    def on_blob(blob, done):
        if fault == "shed" and len(got) == 5:
            raise SessionShed("s", "parked-budget", 1)
        blob.collect(lambda data: (got.append(len(data)), done()))

    a, b = socket.socketpair()
    dec = Decoder()
    dec.blob(on_blob)
    out = {}

    def run():
        try:
            pump.recv_pump(dec, b.fileno(), cap=RA_CAP)
        except BaseException as e:
            out["err"] = e
        out["t"] = time.monotonic()

    t = threading.Thread(target=run, daemon=True)
    try:
        t.start()
        # the peer sends half and then stays open and silent
        threading.Thread(target=_send_quietly, args=(a, wire[:len(wire) // 2]),
                         daemon=True).start()
        if fault == "destroy":
            deadline = time.monotonic() + 10
            while dec.bytes < len(wire) // 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.2)  # the helper's next receive is blocked
            assert _helpers_alive()
            t_destroy = time.monotonic()
            dec.destroy()
        else:
            t_destroy = time.monotonic()
        t.join(5)
        assert not t.is_alive(), "recv_pump did not return"
        assert out["t"] - t_destroy < 3.0
        assert not _helpers_alive()
        os.fstat(b.fileno())  # still ours to close
        b.getsockname()
        if fault == "destroy":
            assert "err" not in out and dec.destroyed
        elif fault == "shed":
            assert isinstance(out["err"], SessionShed) and len(got) == 5
        else:
            assert isinstance(out["err"], OSError)
            assert out["err"].errno == 104
            # every slab received before the faulty one was fed first
            assert dec.bytes == sum(fetched[:6])
    finally:
        a.close()
        b.close()


def test_read_ahead_ends_the_decoder_after_its_last_slab(monkeypatch):
    """EOF: ``decoder.end()`` (and the finalize hook behind it) runs
    once the last received slab has been fed, never before — every
    byte of the wire counted and every frame delivered when it fires."""
    made = _ra_setup(monkeypatch, 2)
    wire, want = _ra_wire("mix")
    got: list = []
    seen = {}

    class Dec(Decoder):
        def end(self, on_finished=None):
            seen["bytes"] = self.bytes
            return super().end(on_finished)

    dec = Dec()
    dec.change(lambda ch, done: (got.append(("c", ch.key)), done()))
    dec.blob(lambda blob, done: blob.collect(
        lambda data: (got.append(("b", _h(data))), done())))
    dec.finalize(lambda done: (seen.__setitem__("delivered", len(got)),
                               done()))
    a, b = socket.socketpair()
    try:
        threading.Thread(target=lambda: (a.sendall(wire),
                                         a.shutdown(socket.SHUT_WR)),
                         daemon=True).start()
        pump.recv_pump(dec, b.fileno(), cap=RA_CAP)
    finally:
        a.close()
        b.close()
    assert made == ["pump-rx"]
    assert dec.finished and seen == {"bytes": len(wire),
                                     "delivered": len(want)}
    assert got == want


def test_a_connection_of_short_reads_never_starts_a_helper(monkeypatch):
    """Adapting, not a knob: a peer that sends a little at a time never
    fills a receive slice, so its connection stays inline — no helper
    thread is ever started for it."""
    monkeypatch.setenv("DAT_PUMP", "native")
    made = []
    real = pump.RecvFan
    monkeypatch.setattr(pump, "RecvFan",
                        lambda *a, **k: (made.append(1), real(*a, **k))[1])
    wire = _changes_wire(0, 400)
    a, b = socket.socketpair()
    got: list = []
    try:
        dec = _ra_decoder(got)

        def trickle():
            for i in range(0, len(wire), 4096):
                a.sendall(wire[i:i + 4096])
                time.sleep(0.002)
            a.shutdown(socket.SHUT_WR)

        threading.Thread(target=trickle, daemon=True).start()
        pump.recv_pump(dec, b.fileno())
    finally:
        a.close()
        b.close()
    assert got == [("c", f"k{i}") for i in range(400)]
    assert made == []


def test_read_ahead_stress_keeps_every_connection_whole(monkeypatch):
    """Twelve connections at once, each on its own session thread with
    its own helper (more threads than cores), under a switch interval
    short enough to interleave every hand-off: each stream's blobs come
    through whole and in order, and no helper outlives its pump."""
    import sys

    made = _ra_setup(monkeypatch, 2)
    n = 12
    wires, wants = [], []
    for i in range(n):
        blobs = [bytes([i, j]) * (9_000 + 997 * ((i + j) % 7))
                 for j in range(30)]
        wants.append([("b", _h(b)) for b in blobs])
        wires.append(b"".join(frame(TYPE_BLOB, b) for b in blobs))
    pairs = [socket.socketpair() for _ in range(n)]
    gots = [[] for _ in range(n)]
    decs = [_ra_decoder(g) for g in gots]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(
            target=pump.recv_pump, args=(dec, b.fileno()),
            kwargs={"cap": RA_CAP}, daemon=True)
            for dec, (_a, b) in zip(decs, pairs)]
        threads += [threading.Thread(
            target=lambda a=a, w=w: (a.sendall(w), a.shutdown(socket.SHUT_WR)),
            daemon=True) for (a, _b), w in zip(pairs, wires)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
        for a, b in pairs:
            a.close()
            b.close()
    for i in range(n):
        assert decs[i].finished and gots[i] == wants[i], f"stream {i}"
    assert made == ["pump-rx"] * n
    assert not _helpers_alive()
