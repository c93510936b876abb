"""The event-driven edge (ISSUE 17): ONE epoll session table.

Every test here is the threaded sidecar test restated against
:class:`~dat_replication_protocol_tpu.edge.EdgeLoop` — same foreign
clients (raw wire bytes from test_wire_fixtures), same structured
record shapes, same staged-overload ladder — proving the C10k rewrite
changed the mechanism and nothing observable.
"""

import hashlib
import selectors
import socket
import threading
import time

import pytest

import dat_replication_protocol_tpu as protocol
from dat_replication_protocol_tpu.edge import EdgeLoop, QOS_PRESETS, \
    serve_edge
from dat_replication_protocol_tpu.edge import loop as edge_loop
from dat_replication_protocol_tpu.hub import ReplicationHub, SessionShed
from dat_replication_protocol_tpu.runtime import native
from dat_replication_protocol_tpu.session import pump as pump_mod
from dat_replication_protocol_tpu.wire.framing import TYPE_BLOB, frame

from test_wire_fixtures import CHANGE_PAYLOAD, SESSION_1, SESSION_4


def _decode_reply(raw: bytes) -> list:
    out = []
    dec = protocol.decode()
    dec.change(lambda ch, done: (out.append(ch), done()))
    dec.write(raw)
    dec.end()
    assert dec.finished
    return out


def _recv_all(sock: socket.socket) -> bytes:
    parts = []
    while True:
        d = sock.recv(65536)
        if not d:
            return b"".join(parts)
        parts.append(d)


def _start_loop(loop: EdgeLoop) -> tuple:
    """Bind + serve on a thread; returns (port, thread)."""
    port = loop.bind("127.0.0.1", 0)
    t = threading.Thread(target=loop.serve, daemon=True)
    t.start()
    return port, t


def test_edge_serves_reference_transcript_session_1():
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, max_sessions=1)
    try:
        port, t = _start_loop(loop)
        c = socket.create_connection(("127.0.0.1", port), timeout=10)
        c.sendall(SESSION_1)
        c.shutdown(socket.SHUT_WR)
        reply = _decode_reply(_recv_all(c))
        c.close()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        hub.close()
    assert len(reply) == 1
    ch = reply[0]
    assert ch.key == "change-0" and ch.subset == "digest:change"
    assert ch.value == hashlib.blake2b(
        CHANGE_PAYLOAD, digest_size=32).digest()


def test_edge_blob_and_change_session_4():
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, max_sessions=1)
    try:
        port, t = _start_loop(loop)
        c = socket.create_connection(("127.0.0.1", port), timeout=10)
        c.sendall(SESSION_4)
        c.shutdown(socket.SHUT_WR)
        reply = _decode_reply(_recv_all(c))
        c.close()
        t.join(timeout=10)
    finally:
        hub.close()
    by_key = {ch.key: ch for ch in reply}
    assert set(by_key) == {"blob-0", "change-0"}
    assert by_key["blob-0"].value == hashlib.blake2b(
        b"hello world", digest_size=32).digest()
    assert by_key["blob-0"].subset == "digest:blob"
    assert by_key["change-0"].value == hashlib.blake2b(
        CHANGE_PAYLOAD, digest_size=32).digest()


def test_edge_protocol_error_closes_connection():
    """Hostile bytes observe the destroy cascade + EOF — never a hang,
    and the loop survives to serve the NEXT session cleanly (the
    neighbor-isolation half of the contract)."""
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, max_sessions=2)
    try:
        port, t = _start_loop(loop)
        c = socket.create_connection(("127.0.0.1", port), timeout=10)
        c.settimeout(15)
        c.sendall(b"\xff" * 64)  # hostile length varint
        assert _recv_all(c) == b""
        c.close()
        # the loop is still alive: a clean session completes after it
        c2 = socket.create_connection(("127.0.0.1", port), timeout=10)
        c2.sendall(SESSION_1)
        c2.shutdown(socket.SHUT_WR)
        reply = _decode_reply(_recv_all(c2))
        c2.close()
        t.join(timeout=10)
        assert len(reply) == 1 and reply[0].key == "change-0"
    finally:
        hub.close()


def test_edge_hub_busy_rejection_is_structured(obs_enabled):
    """Overload stage 1 through the loop: past the hub's admission
    bound the client observes EOF with no reply bytes, the edge counts
    the rejection, and the hub's structured reject event fires — the
    threaded leg's record, byte-for-byte.  The rejection shows up as
    the loop's LABELED registry counter (collector-backed, read off
    the admission attributes) cross-checked against
    ``admission_state()`` — the ISSUE 18 satellite: the fleet
    ``max_rejected`` ceiling reads the registry, so the count must be
    there with the loop live, gate or no gate."""
    from dat_replication_protocol_tpu.obs.events import EVENTS

    hub = ReplicationHub(max_sessions=1)
    held = hub.register("occupant")
    # max_sessions=2 keeps the loop ALIVE after the rejection: the
    # collector unregisters at shutdown, so the registry cross-check
    # below must sample a live loop (the fleet poller's view)
    loop = EdgeLoop(hub, max_sessions=2)
    try:
        port, t = _start_loop(loop)
        c = socket.create_connection(("127.0.0.1", port), timeout=10)
        c.settimeout(15)
        c.sendall(SESSION_1)
        assert _recv_all(c) == b""  # EOF, no decoder, no reply
        c.close()
        deadline = time.monotonic() + 5
        while (loop.admission_state()["rejected"] < 1
                and time.monotonic() < deadline):
            time.sleep(0.01)
        snap = loop.snapshot()
        assert snap["rejected"] == 1 and snap["admitted"] == 0
        recs = [e["fields"] for e in EVENTS.events("sidecar.session")]
        assert recs and recs[-1] == {
            "changes": 0, "blobs": 0, "bytes": 0, "digests": 0,
            "ok": False, "rejected": True, "sessions": 1,
            "parked_bytes": 0}
        name = loop.profiler.name
        counters = obs_enabled.REGISTRY.snapshot()["counters"]
        assert counters[f"edge.rejected{{loop={name}}}"] == 1
        assert counters[f"edge.served{{loop={name}}}"] == 1
        assert counters[f"edge.admitted{{loop={name}}}"] == 0
        assert counters[f"edge.shed{{loop={name}}}"] == 0
        state = loop.admission_state()
        assert state["rejected"] == 1 and state["shed"] == 0
        held.close()
        loop.close()
        t.join(timeout=10)
        assert not t.is_alive()
    finally:
        hub.close()


def test_edge_concurrent_sessions_one_loop(obs_enabled):
    """N concurrent mixed-QoS hub sessions through ONE loop thread:
    every reply byte-exact, the session-table snapshot carries the
    per-class breakdown while they are live, and the per-class gauges
    ride the registry collector (the fleet-plane satellite)."""
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    N = 8
    hub = ReplicationHub(linger_s=0.002)
    qos_of = lambda n, peer, mode: \
        "latency" if n % 2 else "throughput"  # noqa: E731
    loop = EdgeLoop(hub, qos_of=qos_of, max_sessions=N)
    hold = threading.Event()
    results = {}

    def client(i):
        c = socket.create_connection(("127.0.0.1", port), timeout=10)
        half = len(SESSION_4) // 2
        c.sendall(SESSION_4[:half])
        hold.wait(10)  # keep every session parked in the table at once
        c.sendall(SESSION_4[half:])
        c.shutdown(socket.SHUT_WR)
        results[i] = _decode_reply(_recv_all(c))
        c.close()

    try:
        port, t = _start_loop(loop)
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(N)]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            snap = loop.snapshot()
            if snap["sessions"] == N:
                break
            time.sleep(0.01)
        snap = loop.snapshot()
        assert snap["sessions"] == N
        assert snap["by_class"] == {"latency": N // 2,
                                    "throughput": N // 2}
        assert snap["by_kind"] == {"hub": N}
        reg = obs_metrics.snapshot()
        assert reg["gauges"]["edge.sessions"] == float(N)
        assert reg["gauges"]["edge.sessions{class=latency}"] == N // 2
        adm = loop.admission_state()
        assert adm["stage"] == "edge" and adm["open"] is True
        assert adm["hub"]["sessions"] == N
        hold.set()
        for th in threads:
            th.join(15)
            assert not th.is_alive(), "client HANG"
        t.join(timeout=10)
    finally:
        hold.set()
        hub.close()
    blob_digest = hashlib.blake2b(b"hello world", digest_size=32).digest()
    for i in range(N):
        by_key = {ch.key: ch for ch in results[i]}
        assert set(by_key) == {"blob-0", "change-0"}, f"client {i}"
        assert by_key["blob-0"].value == blob_digest


def test_edge_fanout_broadcasts_source_wire_to_subscribers():
    """The --fanout shape through the loop: first connection claims the
    source slot (decoded + digested once), later connections subscribe
    and receive the source's wire byte-exactly — including a late
    joiner served from retention after seal."""
    from dat_replication_protocol_tpu.fanout import FanoutServer

    hub = ReplicationHub(linger_s=0.002)
    fanout = FanoutServer(stall_timeout=10.0)
    loop = EdgeLoop(hub, fanouts={"main": fanout}, max_sessions=3)
    try:
        port, t = _start_loop(loop)
        addr = ("127.0.0.1", port)
        src = socket.create_connection(addr, timeout=10)
        half = len(SESSION_4) // 2
        src.sendall(SESSION_4[:half])
        time.sleep(0.2)  # the claim lands before the subscriber dials
        sub1 = socket.create_connection(addr, timeout=10)
        src.sendall(SESSION_4[half:])
        src.shutdown(socket.SHUT_WR)
        reply = _decode_reply(_recv_all(src))
        src.close()
        by_key = {ch.key: ch for ch in reply}
        assert set(by_key) == {"blob-0", "change-0"}  # digested at source
        sub2 = socket.create_connection(addr, timeout=10)  # late joiner
        got1 = _recv_all(sub1)
        got2 = _recv_all(sub2)
        sub1.close()
        sub2.close()
        t.join(timeout=10)
        assert got1 == SESSION_4  # byte-exact broadcast
        assert got2 == SESSION_4
    finally:
        fanout.close()
        hub.close()


def test_edge_one_hub_serves_n_broadcast_groups():
    """The tentpole's unified-table claim: ONE loop + ONE hub serving
    TWO broadcast groups at once — each group's source digested by the
    shared hub, each group's subscriber byte-exact on ITS OWN wire."""
    from dat_replication_protocol_tpu.fanout import FanoutServer

    hub = ReplicationHub(linger_s=0.002)
    f_a = FanoutServer(stall_timeout=10.0)
    f_b = FanoutServer(stall_timeout=10.0)
    # connections 1+3 -> group a (source, then subscriber); 2+4 -> b
    group_of = lambda n, peer: "a" if n in (1, 3) else "b"  # noqa: E731
    loop = EdgeLoop(hub, fanouts={"a": f_a, "b": f_b},
                    group_of=group_of, max_sessions=4)
    try:
        port, t = _start_loop(loop)
        addr = ("127.0.0.1", port)
        src_a = socket.create_connection(addr, timeout=10)   # n=1
        src_b = socket.create_connection(addr, timeout=10)   # n=2
        time.sleep(0.2)  # both claims land before the subscribers dial
        sub_a = socket.create_connection(addr, timeout=10)   # n=3
        sub_b = socket.create_connection(addr, timeout=10)   # n=4
        src_a.sendall(SESSION_1)
        src_a.shutdown(socket.SHUT_WR)
        src_b.sendall(SESSION_4)
        src_b.shutdown(socket.SHUT_WR)
        reply_a = _decode_reply(_recv_all(src_a))
        reply_b = _decode_reply(_recv_all(src_b))
        src_a.close()
        src_b.close()
        got_a = _recv_all(sub_a)
        got_b = _recv_all(sub_b)
        sub_a.close()
        sub_b.close()
        t.join(timeout=10)
        assert got_a == SESSION_1 and got_b == SESSION_4
        assert {ch.key for ch in reply_a} == {"change-0"}
        assert {ch.key for ch in reply_b} == {"blob-0", "change-0"}
    finally:
        f_a.close()
        f_b.close()
        hub.close()


def test_edge_reconcile_leg_exchanges_exact_diff(tmp_path):
    """The --reconcile responder through the loop: the initiator's
    record shape and O(diff) exchange, identical to the threaded leg."""
    from dat_replication_protocol_tpu import sidecar
    from dat_replication_protocol_tpu.runtime import replay
    from dat_replication_protocol_tpu.runtime.reconcile_driver import (
        RatelessReplica,
        run_initiator,
    )

    def log_bytes(keys):
        return replay.encode_change_log(
            [{"key": k, "change": i, "from": i, "to": i + 1,
              "value": b"v:" + k.encode()} for i, k in enumerate(keys)])

    keys = [f"key-{i:05d}" for i in range(200)]
    logfile = tmp_path / "srv_log.bin"
    logfile.write_bytes(log_bytes(keys + ["srv-only-1", "srv-only-2"]))
    client = RatelessReplica(log_bytes(keys + ["cli-only"]))
    replica = sidecar.load_reconcile_replica(str(logfile))
    loop = EdgeLoop(reconcile_replica=replica, max_sessions=2)
    try:
        port, t = _start_loop(loop)
        for _ in range(2):  # a second session against the same replica
            c = socket.create_connection(("127.0.0.1", port), timeout=10)
            out = run_initiator(
                client, c.recv, c.sendall,
                close_write=lambda c=c: c.shutdown(socket.SHUT_WR))
            c.close()
            assert out["ok"]
            assert out["records_sent"] == 1
            assert {ch.key for ch in out["received"]} == {"srv-only-1",
                                                          "srv-only-2"}
        t.join(timeout=10)
    finally:
        pass


def test_edge_mixed_modes_share_one_session_table(tmp_path):
    """Hub sessions and reconcile responders through the SAME loop and
    the SAME table at the same time — the whole point of the rewrite."""
    from dat_replication_protocol_tpu import sidecar
    from dat_replication_protocol_tpu.runtime import replay
    from dat_replication_protocol_tpu.runtime.reconcile_driver import (
        RatelessReplica,
        run_initiator,
    )

    logfile = tmp_path / "log.bin"
    logfile.write_bytes(replay.encode_change_log(
        [{"key": "srv-only", "change": 0, "from": 0, "to": 1,
          "value": b"v"}]))
    replica = sidecar.load_reconcile_replica(str(logfile))
    client = RatelessReplica([])
    hub = ReplicationHub(linger_s=0.002)
    mode_of = lambda n, peer: "hub" if n == 1 else "reconcile"  # noqa: E731
    loop = EdgeLoop(hub, reconcile_replica=replica, mode_of=mode_of,
                    max_sessions=2)
    box = {}
    try:
        port, t = _start_loop(loop)
        addr = ("127.0.0.1", port)
        hub_c = socket.create_connection(addr, timeout=10)  # n=1: hub
        half = len(SESSION_4) // 2
        hub_c.sendall(SESSION_4[:half])  # park the hub session mid-wire

        def reconcile_leg():
            c = socket.create_connection(addr, timeout=10)  # n=2
            box["out"] = run_initiator(
                client, c.recv, c.sendall,
                close_write=lambda: c.shutdown(socket.SHUT_WR))
            c.close()

        tr = threading.Thread(target=reconcile_leg, daemon=True)
        tr.start()
        tr.join(15)
        assert not tr.is_alive(), "reconcile starved by the hub session"
        assert box["out"]["ok"]
        assert {ch.key for ch in box["out"]["received"]} == {"srv-only"}
        hub_c.sendall(SESSION_4[half:])  # now finish the hub session
        hub_c.shutdown(socket.SHUT_WR)
        reply = _decode_reply(_recv_all(hub_c))
        hub_c.close()
        t.join(timeout=10)
        assert {ch.key for ch in reply} == {"blob-0", "change-0"}
    finally:
        hub.close()


def test_edge_qos_presets_map_onto_hub_weights():
    """The QoS tiers are the existing window/weight presets, not a new
    scheduler: latency outweighs throughput, and its recv slab is the
    small one."""
    assert QOS_PRESETS["latency"]["weight"] > \
        QOS_PRESETS["throughput"]["weight"]
    assert QOS_PRESETS["latency"]["recv_cap"] < \
        QOS_PRESETS["throughput"]["recv_cap"]


def test_serve_edge_ready_cb_and_close():
    """The serve_edge entry point: ready_cb(port) fires once bound, and
    close() from another thread exits the loop promptly."""
    hub = ReplicationHub(linger_s=0.002)
    ready = threading.Event()
    box = {}
    loop = EdgeLoop(hub, tick=0.02)
    loop.bind("127.0.0.1", 0)
    t = threading.Thread(
        target=loop.serve,
        kwargs=dict(ready_cb=lambda p: (box.__setitem__("p", p),
                                        ready.set())),
        daemon=True)
    t.start()
    try:
        assert ready.wait(10)
        assert box["p"] == loop.port
        loop.close()
        t.join(10)
        assert not t.is_alive(), "close() did not stop the loop"
    finally:
        hub.close()


def test_edge_stats_fd_snapshot_carries_edge_aggregate(obs_enabled):
    """The fleet-plane satellite: snapshot_stats() (what --stats-fd and
    /snapshot serve) carries the session-table aggregate while an edge
    loop is active, and /healthz's admission stage is the edge's."""
    from dat_replication_protocol_tpu import sidecar
    from dat_replication_protocol_tpu.obs.http import default_healthz

    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub)
    sidecar.set_active_edge(loop)
    sidecar.set_active_hub(hub)
    try:
        snap = sidecar.snapshot_stats()
        assert snap["edge"]["sessions"] == 0
        assert snap["edge"]["by_class"] == {}
        assert "pump_route" in snap["edge"]
        hz = default_healthz(sidecar._active_admission_fn())
        adm = hz["stages"]["admission"]
        assert adm["stage"] == "edge" and adm["ok"] is True
    finally:
        sidecar.set_active_hub(None)
        sidecar.set_active_edge(None)
        hub.close()


# -- a turn's bulk sessions received side by side (ISSUE 36) -----------------

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library unavailable")


def _read_out(enc) -> bytes:
    parts = []
    while True:
        d = enc.read(1 << 20)
        if not d:
            return b"".join(parts)
        parts.append(bytes(d))


def _stream(i: int, cut: bool = False) -> tuple:
    """Session ``i``'s bytes and what they must come back as:
    ``(wire, expect)`` with ``expect[kind]`` the digests in submit
    order.  Blobs of a few hundred KB (each straddles receive slabs),
    two zero-length blobs (raw frames: the encoder refuses them), a
    run of changes on either side; ``cut`` ends the wire mid-blob."""
    enc = protocol.encode()
    records = []
    for j in range(30):
        rec = {"key": f"s{i}k{j}", "change": j, "from": 0, "to": 1,
               "value": bytes([i, j]) * (j + 1)}
        records.append(rec)
        enc.change(rec)
    wire = _read_out(enc)
    blobs = [bytes([i + 1, j + 1]) * (90_000 + 7_001 * j) for j in range(6)]
    blobs[2:2] = [b""]
    blobs.append(b"")
    wire += b"".join(frame(TYPE_BLOB, b) for b in blobs)
    expect = {
        "blob": [hashlib.blake2b(b, digest_size=32).digest()
                 for b in blobs],
        "change": [hashlib.blake2b(protocol.wire.encode_change(r),
                                   digest_size=32).digest()
                   for r in records]}
    if cut:
        return wire + frame(TYPE_BLOB, b"q" * 50_000)[:20_000], expect
    enc = protocol.encode()
    enc.finalize()
    return wire + _read_out(enc), expect


def _feed_stream(i: int) -> tuple:
    """A change feed: rows of a hundred bytes, trickled."""
    enc = protocol.encode()
    records = [{"key": f"f{i}r{j}", "change": j, "from": 0, "to": 1,
                "value": bytes([j % 251]) * 100} for j in range(200)]
    for rec in records:
        enc.change(rec)
    enc.finalize()
    return _read_out(enc), {"blob": [], "change": [
        hashlib.blake2b(protocol.wire.encode_change(r),
                        digest_size=32).digest() for r in records]}


def _by_kind(reply: list) -> dict:
    out = {"change": [], "blob": []}
    for ch in reply:
        kind, seq = ch.key.split("-")
        assert int(seq) == len(out[kind]), "per-kind order"
        out[kind].append(ch.value)
    return out


def _serve_streams(loop, wires: dict, pace: dict = None) -> dict:
    """Every wire through ``loop`` at once, one client thread each;
    returns the decoded replies (``None`` where the reply was cut)."""
    port, t = _start_loop(loop)
    replies: dict = {}
    go = threading.Barrier(len(wires))

    def client(i, wire):
        c = socket.create_connection(("127.0.0.1", port), timeout=10)
        c.settimeout(20)
        raw = []
        rx = threading.Thread(
            target=lambda: raw.append(_recv_all_or_reset(c)), daemon=True)
        rx.start()
        go.wait(10)
        step = (pace or {}).get(i)
        try:
            if step is None:
                c.sendall(wire)
            else:
                for off in range(0, len(wire), step):
                    c.sendall(wire[off:off + step])
                    time.sleep(0.0005)
            c.shutdown(socket.SHUT_WR)
        except OSError:
            pass  # the loop tore this session down under the send
        rx.join(20)
        c.close()
        replies[i] = raw[0] if raw else None

    threads = [threading.Thread(target=client, args=(i, w), daemon=True)
               for i, w in wires.items()]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30)
        assert not th.is_alive(), "client HANG"
    t.join(timeout=15)
    assert not t.is_alive(), "loop HANG"
    return replies


def _recv_all_or_reset(sock) -> bytes:
    parts = []
    while True:
        try:
            d = sock.recv(1 << 16)
        except OSError:
            return b"".join(parts)
        if not d:
            return b"".join(parts)
        parts.append(d)


def _decode_prefix(raw: bytes) -> list:
    """The complete records of a reply that may end anywhere."""
    out = []
    dec = protocol.decode()
    dec.change(lambda ch, done: (out.append(ch), done()))
    dec.on_error(lambda _e: None)
    dec.write(raw)
    return out


def _fan_counters(metrics) -> tuple:
    c = metrics.snapshot()["counters"]
    return c.get("edge.rx.bytes", 0), c.get("edge.rx.fanned.bytes", 0)


def _no_helper_threads() -> bool:
    return not any(th.name.startswith("edge-rx-")
                   for th in threading.enumerate())


@needs_native
@pytest.mark.parametrize("route", ["fanned", "inline", "python"])
def test_edge_fanned_and_inline_receive_agree(route, monkeypatch,
                                              obs_enabled):
    """The same byte streams — 8 sessions of changes, zero-length
    blobs and blobs that straddle slabs, one of them a trickled change
    feed, one ending mid-frame — through the fanned receive, the
    inline one and the Python route: identical deliveries and digests,
    in order per session.  The slice is shrunk so that a few hundred KB
    a session is bulk; only the fanned route leaves the loop thread."""
    monkeypatch.setenv("DAT_PUMP", "python" if route == "python"
                       else "native")
    monkeypatch.setattr(pump_mod, "PUMP_SLICE", 1 << 15)
    if route == "inline":
        monkeypatch.setattr(EdgeLoop, "_fan_reads",
                            lambda self, events, prof=None: frozenset())
    wires, expects = {}, {}
    for i in range(8):
        wires[i], expects[i] = (_feed_stream(i) if i == 3
                                else _stream(i, cut=(i == 5)))
    hub = ReplicationHub(linger_s=0.002)
    qos_of = lambda n, peer, mode: \
        "latency" if n % 4 == 0 else "throughput"  # noqa: E731
    loop = EdgeLoop(hub, qos_of=qos_of, max_sessions=8, tick=0.01)
    try:
        replies = _serve_streams(loop, wires, pace={3: 2_000})
    finally:
        hub.close()
    for i in range(8):
        if i == 5:
            # EOF mid-frame: a structured teardown; what was answered
            # before it is a prefix of the truth, never a wrong digest
            got = _by_kind(_decode_prefix(replies[i]))
            for kind in got:
                assert got[kind] == expects[i][kind][:len(got[kind])]
            continue
        assert _by_kind(_decode_reply(replies[i])) == expects[i], \
            f"session {i} on route {route}"
    rx, fanned = _fan_counters(obs_enabled)
    assert rx >= sum(len(w) for w in wires.values()) - len(wires[5])
    if route == "fanned":
        assert 0 < fanned <= rx
    else:
        assert fanned == 0
    assert loop._rx_fan is None and _no_helper_threads()
    # ISSUE 37: the read phase's two clocks on every route, and on the
    # native ones the receives' own pair, from whichever thread ran them
    hists = obs_enabled.snapshot()["histograms"]
    pairs = [("edge.turn.read_s", "edge.turn.read_cpu_s")]
    if route != "python":
        pairs.append(("pump.fetch.seconds", "pump.fetch.cpu_seconds"))
    else:
        assert hists["pump.fetch.seconds"]["count"] == 0
    for wall, cpu in pairs:
        # the wall clock on every visit, the CPU clock on one in a few
        assert hists[wall]["count"] >= hists[cpu]["count"] > 0, wall
        assert 0.0 < hists[cpu]["sum"] <= hists[wall]["sum"], wall


@needs_native
@pytest.mark.parametrize("traffic", ["small-reads", "lone-bulk"])
def test_edge_small_reads_and_a_lone_bulk_session_stay_inline(
        traffic, monkeypatch, obs_enabled):
    """Adapting, not a knob: sessions whose reads stay under a slice,
    and a bulk session with no bulk neighbour, never pay a thread
    hand-off — the helpers are not even started."""
    monkeypatch.setenv("DAT_PUMP", "native")
    started = []
    real_fan = pump_mod.RecvFan
    monkeypatch.setattr(
        edge_loop, "RecvFan",
        lambda n: (started.append(n), real_fan(n))[1])
    if traffic == "small-reads":
        streams = {i: _feed_stream(i) for i in range(6)}
        pace = {i: 4_000 for i in streams}
    else:
        monkeypatch.setattr(pump_mod, "PUMP_SLICE", 1 << 15)
        streams = {0: _stream(0), 1: _feed_stream(1), 2: _feed_stream(2)}
        pace = {1: 1_000, 2: 1_000}
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, max_sessions=len(streams), tick=0.01)
    try:
        replies = _serve_streams(loop, {i: s[0] for i, s in streams.items()},
                                 pace=pace)
    finally:
        hub.close()
    for i, (_wire, expect) in streams.items():
        assert _by_kind(_decode_reply(replies[i])) == expect
    rx, fanned = _fan_counters(obs_enabled)
    assert rx == sum(len(s[0]) for s in streams.values())
    assert fanned == 0 and started == []


def _parked_sessions(loop, n: int, payload: bytes) -> tuple:
    """``n`` hub sessions admitted by hand-driven turns, each with
    ``payload`` waiting in its socket; returns (clients, sessions)."""
    port = loop.bind("127.0.0.1", 0)
    clients = [socket.create_connection(("127.0.0.1", port), timeout=10)
               for _ in range(n)]
    deadline = time.monotonic() + 10
    while len(loop._table) < n and time.monotonic() < deadline:
        loop._dark_turn()
    sessions = sorted(loop._table.values(), key=lambda s: s.n)
    assert len(sessions) == n
    for c in clients:
        c.sendall(payload)
    time.sleep(0.05)
    return clients, sessions


@needs_native
@pytest.mark.parametrize("gate", ["decoder-stalled", "hub-window-full"])
def test_edge_closed_gate_is_not_received_that_turn(gate, monkeypatch):
    """The read gate is checked on the loop thread before a receive is
    handed out: a session whose decoder stalled, or whose hub window
    is full, keeps its bytes in the kernel this turn — and with it
    gone from a turn of two, the other is read inline."""
    monkeypatch.setenv("DAT_PUMP", "native")
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, tick=0.01)
    payload = frame(TYPE_BLOB, b"g" * 40_000)
    clients = []
    try:
        clients, (s0, s1, s2) = _parked_sessions(loop, 3, payload)
        for s in (s0, s1, s2):
            s.pump.bulk = True
        if gate == "decoder-stalled":
            monkeypatch.setattr(s1.machine.dec, "writable", lambda: False)
        else:
            monkeypatch.setattr(s1.machine.hub_session, "window_room",
                                lambda: False)
        events = [(selectors.SelectorKey(s.conn, s.fd, s.mask, s),
                   selectors.EVENT_READ) for s in (s0, s1, s2)]
        fanned = loop._fan_reads(events)
        assert fanned == {s0, s2}
        assert s0.machine.dec.bytes == s2.machine.dec.bytes == len(payload)
        assert s1.machine.dec.bytes == 0
        # a turn of two with one gated: nobody is fanned, nothing read
        s0.pump.bulk = True
        clients[0].sendall(payload)
        time.sleep(0.05)
        assert loop._fan_reads(events[:2]) == frozenset()
        assert s0.machine.dec.bytes == len(payload)
        assert s1.machine.dec.bytes == 0
    finally:
        for c in clients:
            c.close()
        loop._shutdown()
        hub.close()
    assert _no_helper_threads()


@needs_native
@pytest.mark.parametrize("fault", ["transport-error", "shed"])
def test_edge_fault_with_receives_in_flight_tears_down_that_session_alone(
        fault, monkeypatch, obs_enabled):
    """A transport error coming back from a helper's call, and a shed
    raised by a feed while the neighbours' receives are still in
    flight, destroy that session's two directions and nobody else's;
    the loop's shutdown leaves no helper behind."""
    from dat_replication_protocol_tpu.obs.events import EVENTS

    monkeypatch.setenv("DAT_PUMP", "native")
    monkeypatch.setattr(pump_mod, "PUMP_SLICE", 1 << 15)
    victim = {}  # the third session admitted, once the table holds it

    if fault == "transport-error":
        real_fetch = pump_mod.recv_fetch

        def fetch(pump):
            buf, r, seconds = real_fetch(pump)
            if threading.current_thread().name.startswith("edge-rx-") \
                    and victim.get("fd") == pump.fd:
                victim["hit"] = True
                return buf, (-104, 0, 0, 0), seconds  # ECONNRESET
            return buf, r, seconds

        monkeypatch.setattr(pump_mod, "recv_fetch", fetch)
    else:
        real_feed = edge_loop.recv_feed

        def feed(pump, dec, fetched, tap=None):
            if victim.get("fd") == pump.fd:
                victim["hit"] = True
                raise SessionShed("victim", "parked-budget", 1)
            return real_feed(pump, dec, fetched, tap)

        monkeypatch.setattr(edge_loop, "recv_feed", feed)

    real_fan_reads = EdgeLoop._fan_reads

    def fan_reads(self, events, prof=None):
        if "fd" not in victim:
            for s in self._table.values():
                if s.n == 3:
                    victim["fd"] = s.fd
        return real_fan_reads(self, events, prof)

    monkeypatch.setattr(EdgeLoop, "_fan_reads", fan_reads)
    streams = {i: _stream(i) for i in range(6)}
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, max_sessions=6, tick=0.01)
    try:
        replies = _serve_streams(loop, {i: s[0] for i, s in streams.items()})
    finally:
        hub.close()
    assert victim.get("hit"), "the fault never fired on a fanned receive"
    records = [e["fields"] for e in EVENTS.events("sidecar.session")]
    assert len(records) == 6
    bad = [r for r in records if not r["ok"]]
    assert len(bad) == 1 and bad[0]["session"].startswith("c3:")
    whole = 0
    for i, (_wire, expect) in streams.items():
        got = _by_kind(_decode_prefix(replies[i]))
        if got == expect:
            whole += 1
        else:
            for kind in got:
                assert got[kind] == expect[kind][:len(got[kind])]
    # the victim's own reply is whole only where the fault met its last
    # receive, everything already answered
    assert whole >= 5, "a neighbour's reply was cut"
    assert _no_helper_threads()
