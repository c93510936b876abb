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
