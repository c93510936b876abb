"""The literal sidecar endpoint (round-4 verdict missing #4 / next #6).

The client side of every test is a FOREIGN client: raw wire bytes on a
socket or pipe — no package Encoder — using the hand-derived reference
transcripts from test_wire_fixtures (their wire, reference:
test/basic.js), so these tests prove a non-Python process could pipe
into the TPU data plane exactly the way the reference pipes into a
socket (reference: example.js:53).
"""

import hashlib
import socket
import subprocess
import sys
import threading
import time

import pytest

import dat_replication_protocol_tpu as protocol
from dat_replication_protocol_tpu import sidecar

from test_wire_fixtures import CHANGE_PAYLOAD, SESSION_1, SESSION_4


def _decode_reply(raw: bytes) -> list:
    """Parse the sidecar's reply stream with an independent decoder."""
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


def test_tcp_sidecar_serves_reference_transcript_session_1():
    ready = threading.Event()
    port_box = {}

    def run():
        sidecar.serve_tcp(
            "127.0.0.1", 0, max_sessions=1,
            ready_cb=lambda p: (port_box.__setitem__("p", p), ready.set()),
        )

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert ready.wait(10)
    c = socket.create_connection(("127.0.0.1", port_box["p"]), timeout=10)
    c.sendall(SESSION_1)  # THEIR bytes: one change frame
    c.shutdown(socket.SHUT_WR)
    reply = _decode_reply(_recv_all(c))
    c.close()
    t.join(timeout=10)
    assert len(reply) == 1
    ch = reply[0]
    assert ch.key == "change-0" and ch.subset == "digest:change"
    assert ch.value == hashlib.blake2b(
        CHANGE_PAYLOAD, digest_size=32).digest()


def test_tcp_sidecar_blob_and_change_session_4():
    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=1,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    c = socket.create_connection(("127.0.0.1", port_box["p"]), timeout=10)
    c.sendall(SESSION_4)  # blob 'hello world' then the parked change
    c.shutdown(socket.SHUT_WR)
    reply = _decode_reply(_recv_all(c))
    c.close()
    by_key = {ch.key: ch for ch in reply}
    assert set(by_key) == {"blob-0", "change-0"}
    assert by_key["blob-0"].value == hashlib.blake2b(
        b"hello world", digest_size=32).digest()
    assert by_key["blob-0"].subset == "digest:blob"
    assert by_key["change-0"].value == hashlib.blake2b(
        CHANGE_PAYLOAD, digest_size=32).digest()


def test_tcp_sidecar_protocol_error_closes_connection():
    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=1,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    c = socket.create_connection(("127.0.0.1", port_box["p"]), timeout=10)
    c.settimeout(15)
    c.sendall(b"\xff" * 64)  # hostile length varint
    # the sidecar must answer with EOF (destroy cascade), never hang
    assert _recv_all(c) == b""
    c.close()
    t.join(timeout=10)


def test_stdio_sidecar_subprocess_roundtrip():
    """The deployment shape itself: a separate OS process, wire bytes on
    stdin, digest session on stdout."""
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # the routing layer's own override pins the child to the host
    # engine — the test exercises the process boundary and wire
    # contract, not the device
    env["DAT_DEVICE_HASH"] = "0"
    proc = subprocess.Popen(
        [sys.executable, "-m", "dat_replication_protocol_tpu.sidecar",
         "--stdio", "--backend", "tpu"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        cwd=repo_root, env=env,
    )
    out, err = proc.communicate(SESSION_4, timeout=120)
    assert proc.returncode == 0, err.decode()
    reply = _decode_reply(out)
    assert {ch.key for ch in reply} == {"blob-0", "change-0"}
    assert all(len(ch.value) == 32 for ch in reply)


def test_tcp_sidecar_survives_client_vanishing_mid_reply():
    """A client that closes its whole socket before reading the reply
    must not hang the session thread or crash the daemon (the sender's
    EPIPE tears down both directions)."""
    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=1,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    c = socket.create_connection(("127.0.0.1", port_box["p"]), timeout=10)
    c.sendall(SESSION_1)
    # vanish entirely: RST-ish close with the reply unread
    c.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                 b"\x01\x00\x00\x00\x00\x00\x00\x00")
    c.close()
    t.join(timeout=30)
    assert not t.is_alive(), "serve loop hung on a vanished client"


def test_run_session_tears_down_stalled_reply_drain():
    """A client that finishes sending but never reads its reply must not
    park the session thread forever (ADVICE.md round 5: the healthy path
    ended in a bare sender.join()).  After drain_timeout with no reply
    progress, run_session destroys the encoder, fires close_write to
    unblock the parked sender (the socket-shutdown EPIPE analogue), and
    returns ok=False — bounded, observable teardown instead of a
    per-connection thread leak."""
    import time

    fed = {"done": False}

    def read_bytes(n):
        if fed["done"]:
            return b""  # EOF: the client finished sending
        fed["done"] = True
        return SESSION_1

    released = threading.Event()
    closed = threading.Event()

    def write_bytes(data):
        if closed.is_set():
            raise OSError("EPIPE")
        # a peer with a full receive window that never reads: the write
        # parks until close_write "shuts the socket down" under it
        released.wait(30)
        raise OSError("EPIPE")

    def close_write():
        closed.set()
        released.set()

    t0 = time.monotonic()
    stats = sidecar.run_session(read_bytes, write_bytes,
                                close_write=close_write,
                                drain_timeout=1.0)
    elapsed = time.monotonic() - t0
    assert closed.is_set(), "stall teardown never fired close_write"
    assert elapsed < 15, f"drain teardown took {elapsed:.1f}s"
    assert stats["ok"] is False  # a stalled session must not report ok
    assert stats["changes"] == 1 and stats["digests"] == 1


def test_slow_upload_then_burst_is_not_torn_down_as_stalled():
    """The mid-session digest-flush stall check must measure the stall
    from when the backpressure wait STARTS, not from the last reply
    byte: a client that uploads quietly for longer than drain_timeout
    (one huge blob, no digest traffic) and then triggers a reply burst
    that crosses the encoder high-water mark is healthy — pre-fix the
    first 0.1s poll compared against the stale progress clock and tore
    the session down with TimeoutError while the client was reading
    promptly (drain-loop parity: serve-side line resets the clock at
    drain entry; this wait did not)."""
    import time

    enc = protocol.encode()
    n = 1400  # digest replies ~60B framed each: crosses the 64 KiB HW
    for i in range(n):
        enc.change({"key": f"k{i}", "change": i, "from": 0, "to": 1,
                    "value": b"x" * 8})
    enc.finalize()
    wire = enc.read()

    state = {"fed": False}

    def read_bytes(_n):
        if state["fed"]:
            return b""
        state["fed"] = True
        # quiet upload stretch longer than drain_timeout, THEN the burst
        time.sleep(2.0)
        return wire

    release = threading.Event()
    writes = []

    def write_bytes(data):
        # healthy-but-momentarily-busy peer: the first write is in
        # flight for ~0.5s (well under drain_timeout) while the digest
        # burst crosses the high-water mark behind it
        if not writes:
            writes.append(len(data))
            release.wait(10)
        else:
            writes.append(len(data))

    threading.Timer(2.5, release.set).start()
    stats = sidecar.run_session(read_bytes, write_bytes,
                                close_write=lambda: None,
                                drain_timeout=1.5)
    assert stats["ok"] is True, f"healthy session torn down: {stats}"
    assert stats["digests"] == n


def test_stall_teardown_with_inflight_digest_batches(monkeypatch):
    """Reply stall while the PIPELINED digest engine (ISSUE 7: jitted
    batch dispatches, prefetched readback) still holds in-flight work:
    the drain teardown must stay bounded — the flush-before-finalize
    barrier parked behind a stalled reply cannot deadlock the session
    thread against its own outstanding batches."""
    import time

    monkeypatch.setenv("DAT_DEVICE_HASH", "1")  # the jitted batch engine

    enc = protocol.encode()
    n = 1200  # enough digest replies to cross the encoder high-water
    for i in range(n):
        enc.change({"key": f"k{i}", "change": i, "from": 0, "to": 1,
                    "value": b"x" * 16})
    enc.finalize()
    wire = enc.read()

    state = {"fed": False}

    def read_bytes(_n):
        if state["fed"]:
            return b""
        state["fed"] = True
        return wire

    released = threading.Event()
    closed = threading.Event()

    def write_bytes(data):
        if closed.is_set():
            raise OSError("EPIPE")
        released.wait(30)  # the client never reads its reply
        raise OSError("EPIPE")

    def close_write():
        closed.set()
        released.set()

    t0 = time.monotonic()
    stats = sidecar.run_session(read_bytes, write_bytes,
                                close_write=close_write,
                                drain_timeout=1.0)
    elapsed = time.monotonic() - t0
    assert closed.is_set(), "stall teardown never fired close_write"
    assert elapsed < 20, f"teardown took {elapsed:.1f}s with batches in flight"
    assert stats["ok"] is False


# -- telemetry (ISSUE 3): stall events + --stats-fd machinery ----------------


def test_stall_teardown_emits_structured_stall_event(obs_enabled):
    """Satellite of ISSUE 3: the reply-drain deadline firing must be
    VISIBLE — a sidecar.stall event with the deadline and reply
    progress, plus the stalls counter — not just a silent teardown."""
    from dat_replication_protocol_tpu.obs.events import EVENTS

    fed = {"done": False}

    def read_bytes(n):
        if fed["done"]:
            return b""
        fed["done"] = True
        return SESSION_1

    released = threading.Event()
    closed = threading.Event()

    def write_bytes(data):
        if closed.is_set():
            raise OSError("EPIPE")
        released.wait(30)
        raise OSError("EPIPE")

    def close_write():
        closed.set()
        released.set()

    stats = sidecar.run_session(read_bytes, write_bytes,
                                close_write=close_write,
                                drain_timeout=0.5)
    assert stats["ok"] is False
    stalls = EVENTS.events("sidecar.stall")
    assert len(stalls) == 1
    assert stalls[0]["fields"]["kind"] == "reply-drain"
    assert stalls[0]["fields"]["seconds"] == 0.5
    assert obs_enabled.REGISTRY.counter("sidecar.stalls").value == 1
    # the session record rides the same event stream
    sessions = EVENTS.events("sidecar.session")
    assert len(sessions) == 1 and sessions[0]["fields"]["ok"] is False


def test_stats_emitter_kick_forces_immediate_parseable_dump(obs_enabled):
    import json
    import os

    obs_enabled.REGISTRY.counter("sidecar.test.marker").inc(7)
    r, w = os.pipe()
    emitter = sidecar.StatsEmitter(w, interval=60.0).start()
    try:
        emitter.kick()
        line = b""
        while not line.endswith(b"\n"):
            line += os.read(r, 65536)
        rec = json.loads(line.decode())
        assert rec["metrics"]["counters"]["sidecar.test.marker"] == 7
        assert "ts" in rec and "monotonic" in rec
        assert "events_dropped" in rec
    finally:
        emitter.stop()
        os.close(r)
        os.close(w)


def test_sigusr1_one_shot_dump(obs_enabled):
    import json
    import os
    import signal

    r, w = os.pipe()
    emitter = sidecar.StatsEmitter(w, interval=60.0).start()
    old = signal.getsignal(signal.SIGUSR1)
    try:
        assert sidecar._install_sigusr1(emitter)
        os.kill(os.getpid(), signal.SIGUSR1)
        line = b""
        while not line.endswith(b"\n"):
            line += os.read(r, 65536)
        rec = json.loads(line.decode())
        assert "metrics" in rec and "counters" in rec["metrics"]
    finally:
        signal.signal(signal.SIGUSR1, old)
        emitter.stop()
        os.close(r)
        os.close(w)


def test_stdio_sidecar_stats_fd_emits_parseable_snapshots():
    """ISSUE 3 acceptance: `sidecar --stats-fd` emits parseable JSON
    snapshots — end-to-end through main(), over a real inherited fd."""
    import json
    import os

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["DAT_DEVICE_HASH"] = "0"
    r, w = os.pipe()
    os.set_inheritable(w, True)
    proc = subprocess.Popen(
        [sys.executable, "-m", "dat_replication_protocol_tpu.sidecar",
         "--stdio", "--stats-fd", str(w), "--stats-interval", "0.2"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        cwd=repo_root, env=env, pass_fds=(w,), close_fds=True,
    )
    os.close(w)
    # a supervisor TAILS its stats pipe: draining it only after exit
    # lets a child that outlives ~10 lines fill the pipe, and the
    # emitter then (by design) tears one record and latches dead
    chunks: list = []

    def _tail() -> None:
        while True:
            chunk = os.read(r, 65536)
            if not chunk:
                return
            chunks.append(chunk)

    tail = threading.Thread(target=_tail, daemon=True)
    tail.start()
    out, err = proc.communicate(SESSION_4, timeout=120)
    assert proc.returncode == 0, err.decode()
    tail.join(timeout=10)
    assert not tail.is_alive()
    os.close(r)
    lines = [ln for ln in b"".join(chunks).decode().splitlines()
             if ln.strip()]
    assert lines, "no stats snapshots emitted"
    for ln in lines:
        rec = json.loads(ln)  # every line parses independently
        assert "metrics" in rec
        # which engine hashes, and on what device, rides every record
        assert rec["device"]["engine"] == "host"
        assert rec["device"]["reason"] == "DAT_DEVICE_HASH=0"
    # the final pre-exit snapshot carries the session's whole story
    final = json.loads(lines[-1])["metrics"]["counters"]
    assert final["sidecar.sessions"] == 1
    assert final["decoder.digests"] == 2  # blob-0 + change-0
    # the reply stream's own encode traffic is attributed too
    assert final["encoder.changes"] == 2


def test_stats_emitter_prom_format_exposition(obs_enabled):
    """ISSUE 4 satellite: --stats-format prom renders Prometheus text
    exposition blocks (cumulative buckets, dat_ namespace)."""
    import os

    obs_enabled.REGISTRY.counter("sidecar.test.prom").inc(3)
    obs_enabled.REGISTRY.histogram("sidecar.test.lat").observe(0.5)
    r, w = os.pipe()
    emitter = sidecar.StatsEmitter(w, interval=60.0, fmt="prom").start()
    try:
        emitter.kick()
        raw = b""
        while b"dat_obs_scrape_ts" not in raw:
            raw += os.read(r, 65536)
        text = raw.decode()
        assert "# TYPE dat_sidecar_test_prom counter\n" \
               "dat_sidecar_test_prom 3" in text
        assert "# TYPE dat_sidecar_test_lat histogram" in text
        assert 'dat_sidecar_test_lat_bucket{le="+Inf"} 1' in text
        assert "dat_obs_events_dropped 0" in text
    finally:
        emitter.stop()
        os.close(r)
        os.close(w)


def test_stats_emitter_rejects_unknown_format():
    import pytest

    with pytest.raises(ValueError):
        sidecar.StatsEmitter(1, fmt="xml")


def test_stdio_sidecar_flight_dir_and_trace_jsonl(tmp_path):
    """ISSUE 4 tentpole wiring: a malformed foreign session through
    `--stdio --flight-dir --trace-jsonl` leaves (a) an atomic
    post-mortem bundle whose manifest carries the error coordinates
    and (b) a JSONL trace log the timeline CLI can consume."""
    import json
    import os

    from dat_replication_protocol_tpu.obs import flight

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["DAT_DEVICE_HASH"] = "0"
    flight_dir = str(tmp_path / "flight")
    trace_log = str(tmp_path / "sidecar.jsonl")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dat_replication_protocol_tpu.sidecar",
         "--stdio", "--flight-dir", flight_dir,
         "--trace-jsonl", trace_log],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, cwd=repo_root, env=env,
    )
    # valid change frame first, then garbage: type id 9 is a wire error
    out, err = proc.communicate(SESSION_1 + b"\x05\x09zzzz", timeout=120)
    assert proc.returncode == 1, err.decode()  # ok: False
    bundles = [n for n in os.listdir(flight_dir)
               if not n.startswith(".")]
    assert len(bundles) == 1 and "protocol-error" in bundles[0], bundles
    b = flight.read_bundle(os.path.join(flight_dir, bundles[0]))
    assert b["manifest"]["error"]["type"] == "ProtocolError"
    assert b["manifest"]["error"]["offset"] is not None
    assert any(e.get("event") == "protocol.error" for e in b["events"])
    # the trace log holds the decoder's wire-offset frame spans
    records = [json.loads(ln)
               for ln in open(trace_log).read().splitlines() if ln]
    frames = [r for r in records if r.get("span") == "decoder.frame"]
    assert frames and frames[0]["fields"]["offset"] == 0


# -- hub mode (ISSUE 8): shared engine, per-session drain + stats ------------


def test_hub_mode_drain_timeout_is_per_session():
    """Satellite of ISSUE 8: in hub mode --drain-timeout applies PER
    SESSION.  Session A stalls its reply and must be torn down at ~its
    own deadline (not extended by B's liveness); session B uploads
    slowly past A's teardown and must complete ok (not cut short by A's
    deadline firing)."""
    import time

    from dat_replication_protocol_tpu.hub import ReplicationHub

    hub = ReplicationHub(linger_s=0.002)
    results = {}

    def session_a():
        fed = {"done": False}

        def read_bytes(n):
            if fed["done"]:
                return b""
            fed["done"] = True
            return SESSION_1

        released = threading.Event()
        closed = threading.Event()

        def write_bytes(data):
            if closed.is_set():
                raise OSError("EPIPE")
            released.wait(30)  # never reads its reply
            raise OSError("EPIPE")

        def close_write():
            closed.set()
            released.set()

        t0 = time.monotonic()
        stats = sidecar.run_session(read_bytes, write_bytes,
                                    close_write=close_write,
                                    drain_timeout=1.0,
                                    hub=hub, session_key="staller")
        results["a"] = (stats, time.monotonic() - t0)

    def session_b():
        state = {"i": 0}
        chunks = [SESSION_4[i:i + 8] for i in range(0, len(SESSION_4), 8)]

        def read_bytes(n):
            # a healthy-but-slow upload: ~2.5s total, well past A's
            # 1s deadline — B's own clock must not be contaminated
            if state["i"] >= len(chunks):
                return b""
            time.sleep(2.5 / len(chunks))
            chunk = chunks[state["i"]]
            state["i"] += 1
            return chunk

        reply = []
        t0 = time.monotonic()
        stats = sidecar.run_session(read_bytes, reply.append,
                                    close_write=lambda: None,
                                    drain_timeout=1.0,
                                    hub=hub, session_key="slowpoke")
        results["b"] = (stats, time.monotonic() - t0)

    ta = threading.Thread(target=session_a, daemon=True)
    tb = threading.Thread(target=session_b, daemon=True)
    ta.start()
    tb.start()
    ta.join(20)
    tb.join(20)
    assert not ta.is_alive() and not tb.is_alive(), "HANG"
    hub.close()
    stats_a, elapsed_a = results["a"]
    stats_b, elapsed_b = results["b"]
    # A: torn down on ITS deadline — not extended while B kept running
    assert stats_a["ok"] is False and stats_a["session"] == "staller"
    assert elapsed_a < 2.4, f"A's teardown waited on B: {elapsed_a:.1f}s"
    # B: completed past A's teardown — not cut short by A's deadline
    assert stats_b["ok"] is True, f"B torn down by A's deadline: {stats_b}"
    assert stats_b["session"] == "slowpoke"
    assert stats_b["digests"] == 2
    assert elapsed_b > 2.0


def test_hub_mode_stats_fd_lines_carry_sessions_breakdown(obs_enabled):
    """Satellite of ISSUE 8: --stats-fd snapshots in hub mode carry a
    per-session `sessions` breakdown that cross-checks against the
    hub's own per-session stats (the oracle contract)."""
    import json
    import os

    from dat_replication_protocol_tpu.hub import ReplicationHub

    hub = ReplicationHub(hash_batch=lambda ps: [
        hashlib.blake2b(p, digest_size=32).digest() for p in ps])
    sidecar.set_active_hub(hub)
    try:
        a = hub.register("peer-a")
        b = hub.register("peer-b")
        got = []
        for i in range(9):
            a.submit(b"payload-%d" % i, lambda d: got.append(d))
        a.flush()
        r, w = os.pipe()
        emitter = sidecar.StatsEmitter(w, interval=60.0).start()
        try:
            emitter.kick()
            line = b""
            while not line.endswith(b"\n"):
                line += os.read(r, 65536)
            rec = json.loads(line.decode())
        finally:
            emitter.stop()
            os.close(r)
            os.close(w)
        # the line's breakdown == the hub's live per-session stats
        assert rec["hub"]["sessions"] == 2
        per = rec["sessions"]
        assert set(per) == {"peer-a", "peer-b"}
        assert per["peer-a"]["submitted"] == 9
        assert per["peer-a"]["delivered"] == 9
        assert per["peer-b"]["submitted"] == 0
        assert per["peer-a"] == hub.sessions_snapshot()["peer-a"]
        # the registry snapshot in the SAME line carries the labeled
        # per-session collector entries (hub.session.* family)
        counters = rec["metrics"]["counters"]
        assert counters["hub.session.submitted{session=peer-a}"] == 9
        assert rec["metrics"]["gauges"]["hub.sessions"] == 2.0
        a.close()
        b.close()
    finally:
        sidecar.set_active_hub(None)
        hub.close()


def test_hub_mode_session_record_cross_checks_driver_stats(obs_enabled):
    """The conformance-oracle arm: run_session's returned driver stats,
    the sidecar.session event, and the hub's dispatch counters must all
    tell the same story for a keyed hub session."""
    from dat_replication_protocol_tpu.hub import ReplicationHub
    from dat_replication_protocol_tpu.obs.events import EVENTS

    hub = ReplicationHub(linger_s=0.002)
    try:
        fed = {"done": False}

        def read_bytes(n):
            if fed["done"]:
                return b""
            fed["done"] = True
            return SESSION_4

        reply = []
        stats = sidecar.run_session(read_bytes, reply.append,
                                    close_write=lambda: None,
                                    hub=hub, session_key="oracle-k")
        assert stats["ok"] is True
        assert stats["session"] == "oracle-k" and stats["shed"] is None
        assert stats["digests"] == 2  # blob-0 + change-0
        ev = EVENTS.events("sidecar.session")[-1]["fields"]
        assert ev["session"] == "oracle-k"
        assert ev["digests"] == stats["digests"]
        reg = obs_enabled.REGISTRY
        assert reg.counter("hub.dispatch.items").value == stats["digests"]
        assert reg.counter("hub.admitted").value == 1
        # the slot was released at session end (bounded cardinality)
        assert hub.sessions_snapshot() == {}
    finally:
        hub.close()


def test_hub_mode_admission_rejection_is_structured(obs_enabled):
    """A connection past the admission bound gets a structured
    rejection record and EOF — no decoder, no queue growth."""
    from dat_replication_protocol_tpu.hub import ReplicationHub
    from dat_replication_protocol_tpu.obs.events import EVENTS

    hub = ReplicationHub(max_sessions=1)
    try:
        held = hub.register("occupant")
        closed = []
        stats = sidecar.run_session(
            lambda n: SESSION_1, lambda d: None,
            close_write=lambda: closed.append(True),
            hub=hub, session_key="refused")
        assert stats == {"changes": 0, "blobs": 0, "bytes": 0,
                         "digests": 0, "ok": False, "rejected": True,
                         "sessions": 1, "parked_bytes": 0}
        assert closed, "rejected connection was not closed"
        rejects = EVENTS.events("hub.reject")
        assert rejects and rejects[-1]["fields"]["key"] == "refused"
        held.close()
    finally:
        hub.close()


# -- fan-out mode (ISSUE 9) ---------------------------------------------------


def test_tcp_sidecar_fanout_broadcasts_source_wire_to_subscribers():
    """--fanout shape: the FIRST connection is the source session
    (decoded + digested once, reply streamed back); later connections
    are subscribers that receive the source's wire bytes byte-exactly
    via the zero-copy writev fan-out — including a late joiner that
    attaches mid-stream."""
    from dat_replication_protocol_tpu.fanout import FanoutServer

    fanout = FanoutServer(stall_timeout=10.0)
    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=3, fanout=fanout,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    addr = ("127.0.0.1", port_box["p"])

    src = socket.create_connection(addr, timeout=10)
    half = len(SESSION_4) // 2
    src.sendall(SESSION_4[:half])

    # subscriber 1 joins mid-stream (offset 0 is still retained)
    sub1 = socket.create_connection(addr, timeout=10)

    src.sendall(SESSION_4[half:])
    src.shutdown(socket.SHUT_WR)
    reply = _decode_reply(_recv_all(src))
    src.close()
    by_key = {ch.key: ch for ch in reply}
    assert set(by_key) == {"blob-0", "change-0"}  # digested ONCE, at source

    # late joiner: the source may already be sealed — retention serves it
    sub2 = socket.create_connection(addr, timeout=10)

    got1 = _recv_all(sub1)
    got2 = _recv_all(sub2)
    sub1.close()
    sub2.close()
    t.join(timeout=10)
    fanout.close()
    assert got1 == SESSION_4  # byte-exact broadcast
    assert got2 == SESSION_4


def test_fanout_subscriber_past_retention_gets_snapshot_needed():
    """A joiner below the retained window gets the structured
    snapshot-needed record and EOF — never silently wrong bytes."""
    import json as _json

    from dat_replication_protocol_tpu.fanout import FanoutServer

    fanout = FanoutServer(retention_budget=64, stall_timeout=5.0)
    try:
        fanout.publish(b"x" * 400)  # budget-trims the head immediately
        fanout.log.enforce_retention()
        a, b = socket.socketpair()
        out = sidecar.run_subscriber(a, fanout, key="late")
        assert out["ok"] is False and out["snapshot_needed"] is True
        assert out["retained"] == [400 - 64, 400]
        line = _recv_all(b)
        rec = _json.loads(line.decode())
        assert rec["snapshot_needed"] is True
        assert rec["retained"] == [336, 400]
        a.close()
        b.close()
    finally:
        fanout.close()


def test_fanout_stats_snapshot_carries_peer_breakdown(obs_enabled):
    """--stats-fd lines in fan-out mode answer "which peer is lagging":
    the snapshot carries the fan-out aggregate and per-peer stats, and
    the registry collector exposes labeled per-peer series."""
    from dat_replication_protocol_tpu.fanout import FanoutServer
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    fanout = FanoutServer(stall_timeout=5.0)
    sidecar.set_active_fanout(fanout)
    try:
        got = bytearray()

        def sink(views):
            n = 0
            for v in views:
                got.extend(bytes(v))
                n += len(v)
            return n

        peer = fanout.attach_peer("k1", sink=sink)
        fanout.publish(b"z" * 5000)
        fanout.seal()
        assert fanout.drain(10)
        snap = sidecar.snapshot_stats()
        assert snap["fanout"]["peers"] == 1
        assert snap["fanout"]["sealed"] is True
        assert snap["peers"]["k1"]["sent_bytes"] == 5000
        assert snap["peers"]["k1"]["shed"] is None
        reg_snap = obs_metrics.snapshot()
        assert reg_snap["counters"]["fanout.peer.sent_bytes{peer=k1}"] == 5000
        assert reg_snap["gauges"]["fanout.peers"] == 1.0
        peer.close()
        assert bytes(got) == b"z" * 5000
    finally:
        sidecar.set_active_fanout(None)
        fanout.close()


def test_fanout_probe_connection_does_not_brick_the_broadcast():
    """Review regression: a stray first connection that closes without
    publishing a byte (healthcheck, port scan) must RELEASE the source
    claim — the real source connecting afterwards still broadcasts."""
    from dat_replication_protocol_tpu.fanout import FanoutServer

    fanout = FanoutServer(stall_timeout=10.0)
    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=3, fanout=fanout,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    addr = ("127.0.0.1", port_box["p"])

    probe = socket.create_connection(addr, timeout=10)
    probe.close()  # the healthcheck: no bytes, instant close
    time.sleep(0.3)  # let its session thread release the claim
    assert not fanout.log.sealed

    src = socket.create_connection(addr, timeout=10)
    src.sendall(SESSION_1)
    src.shutdown(socket.SHUT_WR)
    reply = _decode_reply(_recv_all(src))
    src.close()
    assert len(reply) == 1  # the REAL source was decoded + digested

    sub = socket.create_connection(addr, timeout=10)
    got = _recv_all(sub)
    sub.close()
    t.join(timeout=10)
    fanout.close()
    assert got == SESSION_1


def test_fanout_idle_subscriber_disconnect_releases_slot():
    """Review regression: a caught-up subscriber that disconnects while
    the broadcast is idle (no bytes in flight to surface an EPIPE) must
    release its peer slot instead of leaking it until new traffic."""
    from dat_replication_protocol_tpu.fanout import FanoutServer

    fanout = FanoutServer(stall_timeout=30.0)
    fanout.publish(b"x" * 1000)  # subscribers catch up, log stays open
    try:
        a, b = socket.socketpair()
        out = {}

        def run():
            out["stats"] = sidecar.run_subscriber(a, fanout, key="ghost")

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # wait until the broadcast reached the subscriber
        deadline = time.monotonic() + 5
        got = bytearray()
        b.settimeout(5)
        while len(got) < 1000 and time.monotonic() < deadline:
            got.extend(b.recv(4096))
        assert bytes(got) == b"x" * 1000
        b.close()  # client goes away; the log is idle and unsealed
        t.join(10)
        assert not t.is_alive(), "subscriber thread leaked"
        assert fanout.peers_snapshot() == {}  # the slot was released
        a.close()
    finally:
        fanout.close()


def test_fanout_rejected_subscriber_gets_structured_record():
    """Review regression: a FanoutBusy rejection must SEND its
    structured record — a bare EOF is indistinguishable from an empty
    sealed broadcast."""
    import json as _json

    from dat_replication_protocol_tpu.fanout import FanoutServer

    fanout = FanoutServer(max_peers=1, stall_timeout=5.0)
    try:
        held = fanout.attach_peer("occupant", sink=lambda vs: 0)
        a, b = socket.socketpair()
        out = sidecar.run_subscriber(a, fanout, key="refused")
        assert out["ok"] is False and out["rejected"] is True
        assert out["peers"] == 1 and out["max_peers"] == 1
        rec = _json.loads(_recv_all(b).decode())
        assert rec["rejected"] is True and rec["max_peers"] == 1
        a.close()
        b.close()
        held.close()
    finally:
        fanout.close()


def test_fanout_misrouted_source_fails_loudly_not_silently():
    """Review regression: a subscriber connection that SENDS data is a
    source that lost the claim race — it must get a structured
    not_source record and EOF, never have its session silently
    discarded."""
    import json as _json

    from dat_replication_protocol_tpu.fanout import FanoutServer

    fanout = FanoutServer(stall_timeout=10.0)
    try:
        a, b = socket.socketpair()
        out_box = {}

        def run():
            out_box["out"] = sidecar.run_subscriber(a, fanout, key="mis")

        t = threading.Thread(target=run, daemon=True)
        t.start()
        time.sleep(0.2)
        b.sendall(SESSION_1)  # "I am a source" — wrong slot
        t.join(10)
        assert not t.is_alive()
        out = out_box["out"]
        assert out["ok"] is False and out["not_source"] is True
        raw = _recv_all(b)
        rec = _json.loads(raw.splitlines()[-1].decode())
        assert rec["not_source"] is True
        assert fanout.peers_snapshot() == {}  # slot released
        a.close()
        b.close()
    finally:
        fanout.close()


# -- anti-entropy mode (ISSUE 10) ---------------------------------------------


def test_tcp_sidecar_reconcile_exchanges_exact_diff(tmp_path):
    """--reconcile shape: the daemon answers a reconcile initiator from
    a change-log wire file — the two sides exchange exactly their
    differing records over O(diff) wire, and every extra connection is
    its own independent session against the shared (read-only)
    replica."""
    from dat_replication_protocol_tpu.runtime import replay
    from dat_replication_protocol_tpu.runtime.reconcile_driver import (
        RatelessReplica,
        run_initiator,
    )

    def log_bytes(keys):
        return replay.encode_change_log(
            [{"key": k, "change": i, "from": i, "to": i + 1,
              "value": b"v:" + k.encode()} for i, k in enumerate(keys)])

    keys = [f"key-{i:05d}" for i in range(400)]
    logfile = tmp_path / "srv_log.bin"
    logfile.write_bytes(log_bytes(keys + ["srv-only-1", "srv-only-2"]))
    client = RatelessReplica(log_bytes(keys + ["cli-only"]))

    replica = sidecar.load_reconcile_replica(str(logfile))
    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=2, reconcile_replica=replica,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    addr = ("127.0.0.1", port_box["p"])

    for _ in range(2):  # a second session against the same replica
        c = socket.create_connection(addr, timeout=10)
        out = run_initiator(
            client, c.recv, c.sendall,
            close_write=lambda c=c: c.shutdown(socket.SHUT_WR))
        c.close()
        assert out["ok"]
        assert out["records_sent"] == 1  # cli-only, requested by the daemon
        assert {ch.key for ch in out["received"]} == {"srv-only-1",
                                                      "srv-only-2"}
    t.join(timeout=10)


def test_sidecar_reconcile_corrupt_stream_fails_structured():
    """A garbage initiator against --reconcile observes the FAIL frame
    + EOF; the session record carries the structured error — never a
    hang (the reconcile failure contract at the daemon edge)."""
    from dat_replication_protocol_tpu.runtime.reconcile_driver import (
        RatelessReplica,
    )
    from dat_replication_protocol_tpu.session.transport import once
    from dat_replication_protocol_tpu.wire import reconcile_codec as rcc
    from dat_replication_protocol_tpu.wire.framing import (
        TYPE_RECONCILE,
        frame,
    )

    replica = RatelessReplica([
        {"key": "a", "change": 1, "from": 0, "to": 1, "value": b"x"}])
    a, b = socket.socketpair()
    a.settimeout(10)
    b.settimeout(10)
    box = {}

    def serve():
        box["out"] = sidecar.run_reconcile_session(
            b.recv, b.sendall,
            once(lambda: b.shutdown(socket.SHUT_WR)), replica)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    # symbols with a bad subtype byte: structural corruption
    a.sendall(frame(TYPE_RECONCILE, rcc.encode_begin(1)))
    a.sendall(frame(TYPE_RECONCILE, bytes([99, 1, 2, 3])))
    a.shutdown(socket.SHUT_WR)
    _recv_all(a)  # daemon closes its side: EOF, not a hang
    t.join(10)
    assert not t.is_alive()
    out = box["out"]
    assert out["reconcile"] is True and out["ok"] is False
    assert "error" in out
    a.close()
    b.close()


# -- snapshot bootstrap mode (ISSUE 12) --------------------------------------


def _snapshot_dataset(n=1 << 18, seed=0):
    import numpy as np

    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def test_tcp_sidecar_snapshot_serves_cold_and_stale_joiners(tmp_path):
    """--snapshot shape: the daemon materializes DATAFILE once and
    every connection is an independent joiner session — a cold joiner
    streams the shared full-manifest log, a stale one reconciles and
    moves O(diff) bytes."""
    import numpy as np

    from dat_replication_protocol_tpu.runtime.snapshot_driver import (
        run_snapshot_joiner,
    )

    data = _snapshot_dataset()
    datafile = tmp_path / "dataset.bin"
    datafile.write_bytes(data.tobytes())
    source = sidecar.load_snapshot_source(str(datafile), wire_offset=99)
    stale = data.copy()
    stale[:: len(data) // 8] ^= 0x5A  # a few divergent chunks

    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp,
        args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=2, snapshot_source=source,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True,
    )
    t.start()
    assert ready.wait(10)
    addr = ("127.0.0.1", port_box["p"])

    c = socket.create_connection(addr, timeout=10)
    cold = run_snapshot_joiner(
        c.recv, c.sendall, lambda: c.shutdown(socket.SHUT_WR))
    c.close()
    assert cold["data"] == data.tobytes()
    assert cold["wire_offset"] == 99  # where the live session attaches

    c = socket.create_connection(addr, timeout=10)
    out = run_snapshot_joiner(
        c.recv, c.sendall, lambda: c.shutdown(socket.SHUT_WR),
        have=stale.tobytes())
    c.close()
    assert out["data"] == data.tobytes()
    assert out["chunks_reused"] > 0
    assert out["bytes_received"] < len(data) // 2  # O(diff), not O(n)
    t.join(timeout=10)
    assert np.array_equal(source._buf, data)  # source untouched


def test_fanout_snapshot_needed_record_carries_hint_and_redirect_works(
        tmp_path):
    """The composition aha (ISSUE 12): a subscriber trimmed past the
    broadcast window gets the structured snapshot-needed record WITH
    the bootstrap hint, dials the hinted port, and assembles the
    dataset — no out-of-band config anywhere."""
    import json as _json

    from dat_replication_protocol_tpu.fanout import FanoutServer
    from dat_replication_protocol_tpu.runtime.snapshot_driver import (
        run_snapshot_joiner,
    )
    from dat_replication_protocol_tpu.wire.framing import CAP_SNAPSHOT

    data = _snapshot_dataset(1 << 16, seed=3)
    datafile = tmp_path / "dataset.bin"
    datafile.write_bytes(data.tobytes())
    source = sidecar.load_snapshot_source(str(datafile))

    listener = sidecar.SnapshotListener(source, "127.0.0.1", 0)
    fanout = FanoutServer(retention_budget=64, stall_timeout=5.0,
                          snapshot_hint={"port": listener.port,
                                         "cap": CAP_SNAPSHOT})
    try:
        fanout.publish(b"x" * 400)  # budget-trims the head immediately
        fanout.log.enforce_retention()
        a, b = socket.socketpair()
        out = sidecar.run_subscriber(a, fanout, key="late")
        assert out["ok"] is False and out["snapshot_needed"] is True
        assert out["hint"] == {"port": listener.port, "cap": CAP_SNAPSHOT}
        rec = _json.loads(_recv_all(b).decode())
        a.close()
        b.close()
        assert rec["snapshot_needed"] is True
        assert rec["hint"]["cap"] == CAP_SNAPSHOT

        # ... and the hint WORKS: dial it, bootstrap, done
        c = socket.create_connection(("127.0.0.1", rec["hint"]["port"]),
                                     timeout=10)
        got = run_snapshot_joiner(
            c.recv, c.sendall, lambda: c.shutdown(socket.SHUT_WR))
        c.close()
        assert got["data"] == data.tobytes()
    finally:
        listener.close()
        fanout.close()


def test_sidecar_snapshot_cli_flags(capsys):
    """--snapshot refuses the modes it cannot compose with, keeping the
    CLI contract explicit."""
    import pytest

    with pytest.raises(SystemExit):
        sidecar.main(["--stdio", "--snapshot", "x.bin", "--hub"])
    err = capsys.readouterr().err
    assert "--snapshot cannot combine" in err


# -- blocking-reachability regression tests (ISSUE 16) ------------------------
# The readiness certifier (artifacts/event_loop_surface.json) found two
# true positives: StatsEmitter's EAGAIN/deadline machinery only engages
# on a NONBLOCKING fd, and the subscriber refusal path sendall()'d on a
# default-blocking socket.  These tests prove the bounds are real — on
# the pre-fix code both hang forever, so each runs the suspect call on
# a daemon thread and asserts it RETURNS instead of letting a
# regression wedge the whole suite.


def test_stats_emitter_full_pipe_skips_within_grace_bound():
    """A stats pipe nobody drains must cost one 2 s grace period and a
    clean skip — not a parked emitter thread (the certifier's StatsEmitter
    true positive: os.write on a blocking pipe ignores the deadline)."""
    import json
    import os

    r, w = os.pipe()
    emitter = sidecar.StatsEmitter(w, interval=60.0)  # thread NOT started
    try:
        # fill the pipe to the last byte so the very first write gets
        # EAGAIN (a partial first write would latch the torn-line arm,
        # which is a different — also bounded — path)
        assert not os.get_blocking(w), (
            "StatsEmitter must flip its fd nonblocking up front; a "
            "blocking pipe makes the 2 s grace period fictional")
        for chunk in (65536, 1):
            while True:
                try:
                    os.write(w, b"x" * chunk)
                except BlockingIOError:
                    break
        result = {}
        t = threading.Thread(
            target=lambda: result.update(
                ok=emitter.dump_once(), took=time.monotonic() - t0),
            daemon=True)
        t0 = time.monotonic()
        t.start()
        t.join(timeout=10)
        assert not t.is_alive(), (
            "dump_once wedged on a full pipe — the grace bound is gone")
        # clean skip: nothing of the record was written, emitter alive
        assert result["ok"] is True
        assert result["took"] < 8
        # the skip must not have latched the emitter dead: drain the
        # filler and the next dump emits a complete JSON line
        os.set_blocking(r, False)
        while True:
            try:
                if not os.read(r, 65536):
                    break
            except BlockingIOError:
                break
        assert emitter.dump_once() is True
        line = b""
        while not line.endswith(b"\n"):
            line += os.read(r, 65536)
        rec = json.loads(line[line.index(b"{"):].decode())
        assert "metrics" in rec
    finally:
        os.close(r)
        os.close(w)


def test_refusal_send_to_wedged_subscriber_is_bounded(monkeypatch):
    """A refusal record sent to a subscriber that never reads must give
    up after _REFUSAL_SEND_TIMEOUT — the accept loop runs refusals
    inline, so an unbounded sendall here wedges admission for every
    later subscriber (the certifier's subscriber-path true positive)."""
    monkeypatch.setattr(sidecar, "_REFUSAL_SEND_TIMEOUT", 0.5)
    a, b = socket.socketpair()
    try:
        # shrink both kernel buffers so a fat record overfills them;
        # b is never read — the classic wedged-peer shape
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        out = {"type": "refusal", "reason": "fanout_busy",
               "detail": "x" * (1 << 20)}
        t = threading.Thread(
            target=sidecar._send_refusal, args=(a, out), daemon=True)
        t0 = time.monotonic()
        t.start()
        t.join(timeout=10)
        assert not t.is_alive(), (
            "_send_refusal wedged on an unread socket — the send "
            "timeout bound is gone")
        assert time.monotonic() - t0 < 8
    finally:
        a.close()
        b.close()


def test_replica_stats_record_carries_link_age_and_suspicion():
    """The ISSUE 19 satellite: the gossip record snapshot_stats carries
    grows per-peer ``last_success_age_s`` (None until the first
    success, then a growing age — a silently-dead link is an age, not
    a frozen counter) and the node's cumulative ``suspicion``, both
    cross-checked against the driver's own state."""
    from dat_replication_protocol_tpu.cluster import ReplicaNode
    from dat_replication_protocol_tpu.cluster.live import GossipDriver

    node = ReplicaNode("stats-live", ())
    driver = GossipDriver(node, ["127.0.0.1:1", "127.0.0.1:2"],
                          interval=0.05, seed=0)  # never .start()ed
    driver._last_success["127.0.0.1:1"] = time.monotonic() - 2.0
    node._suspect["127.0.0.1:2"] = 3
    sidecar.set_active_gossip(driver)
    try:
        snap = sidecar.snapshot_stats()
        peers = snap["gossip"]["peers"]
        age = peers["127.0.0.1:1"]["last_success_age_s"]
        assert age is not None and 1.9 <= age < 30.0
        assert peers["127.0.0.1:1"]["suspicion"] == 0
        assert peers["127.0.0.1:2"]["last_success_age_s"] is None
        assert peers["127.0.0.1:2"]["suspicion"] == 3
        # ages GROW between snapshots (same driver, no new success)
        snap2 = sidecar.snapshot_stats()
        assert snap2["gossip"]["peers"]["127.0.0.1:1"][
            "last_success_age_s"] >= age
    finally:
        sidecar.set_active_gossip(None)


def test_snapshot_stats_propagation_section_is_presence_gated():
    """The propagation section rides the replica-mode gossip record
    only: an empty board stays OUT (so the fleet's loud-failure rule
    can tell a dark plane from "no exchanges yet"), a populated board
    rides along verbatim."""
    from dat_replication_protocol_tpu.cluster import ReplicaNode
    from dat_replication_protocol_tpu.obs.propagation import PROPAGATION

    PROPAGATION.reset_for_tests()
    sidecar.set_active_gossip(ReplicaNode("stats-prop", ()))
    try:
        snap = sidecar.snapshot_stats()
        assert "gossip" in snap and "propagation" not in snap
        PROPAGATION.record("stats-a", "stats-b", role="initiator",
                           rnd=1, outcome="progress", seconds=0.01,
                           diff=2, repair_bytes=64)
        snap = sidecar.snapshot_stats()
        link = snap["propagation"]["links"]["stats-a->stats-b"]
        assert link["divergence_records"] == 2
        assert link["divergence_bytes"] == 64
        assert snap["propagation"]["exchange_seconds"]["count"] == 1
    finally:
        sidecar.set_active_gossip(None)
        PROPAGATION.reset_for_tests()


@pytest.mark.parametrize("depth", [1, 2])
def test_tcp_sidecar_reads_a_bulk_session_ahead(monkeypatch, depth):
    """ISSUE 39: a `--tcp` connection streaming 1 MiB blobs fills its
    receives, so its session thread feeds while a helper receives up to
    `depth` slabs ahead — and the reply is the same: every blob's digest
    equal to hashlib's, in order, and (the fan-out tap, fed on the
    session thread in slab order) a subscriber's copy of the wire equal
    to the wire, byte for byte."""
    from dat_replication_protocol_tpu.fanout import FanoutServer
    from dat_replication_protocol_tpu.session import pump
    from dat_replication_protocol_tpu.wire.framing import TYPE_BLOB, frame

    monkeypatch.setenv("DAT_PUMP", "native")
    if pump.effective_pump_route() != "native":
        pytest.skip("no native pump library on this machine")
    monkeypatch.setattr(pump, "PUMP_SLICE", 64 << 10)
    monkeypatch.setattr(pump, "READAHEAD", depth)
    made = []
    real_fan = pump.RecvFan
    monkeypatch.setattr(pump, "RecvFan", lambda *a, **k: (
        made.append(k.get("name")), real_fan(*a, **k))[1])
    blobs = [bytes((b + 29 * i) & 0xFF for b in range(256)) * 4096
             for i in range(12)]
    wire = b"".join(frame(TYPE_BLOB, b) for b in blobs)
    fanout = FanoutServer(stall_timeout=20.0)
    ready = threading.Event()
    port_box = {}
    t = threading.Thread(
        target=sidecar.serve_tcp, args=("127.0.0.1", 0),
        kwargs=dict(max_sessions=2, fanout=fanout,
                    ready_cb=lambda p: (port_box.__setitem__("p", p),
                                        ready.set())),
        daemon=True)
    t.start()
    try:
        assert ready.wait(10)
        addr = ("127.0.0.1", port_box["p"])
        src = socket.create_connection(addr, timeout=30)
        sub = socket.create_connection(addr, timeout=30)
        sender = threading.Thread(
            target=lambda: (src.sendall(wire), src.shutdown(socket.SHUT_WR)),
            daemon=True)
        sender.start()
        reply = _decode_reply(_recv_all(src))
        sender.join(10)
        src.close()
        got = _recv_all(sub)
        sub.close()
        t.join(timeout=10)
    finally:
        fanout.close()
    assert [ch.key for ch in reply] == [f"blob-{i}" for i in range(12)]
    assert [ch.value for ch in reply] == [
        hashlib.blake2b(b, digest_size=32).digest() for b in blobs]
    assert got == wire
    assert made == ["pump-rx"]
