"""Unit coverage of the fault-and-recovery layer's parts.

The sweep (test_session_faults.py) proves the whole; these tests pin
each part's contract: checkpoint contents, journal window semantics,
backoff policy math, the transport pump's immediate drain wakeup (the
lost-wakeup fix), the fd close-once guard, the sidecar's retry flags,
and the asyncio reconnect face.
"""

from __future__ import annotations

import asyncio
import os
import threading
import time

import pytest

import dat_replication_protocol_tpu as protocol
from dat_replication_protocol_tpu.session import transport
from dat_replication_protocol_tpu.session.aio import (
    open_connection_with_retry,
    send_over_async,
)
from dat_replication_protocol_tpu.session.reconnect import (
    BackoffPolicy,
    retrying,
)
from dat_replication_protocol_tpu.session.resume import (
    ResumeError,
    SessionCheckpoint,
    WireJournal,
)
from dat_replication_protocol_tpu.wire.framing import ProtocolError


# -- SessionCheckpoint ------------------------------------------------------

def test_checkpoint_tracks_the_coupled_cursor_tuple():
    e, d = protocol.encode(), protocol.decode()
    d.change(lambda c, done: done())
    d.blob(lambda b, done: (b.on_data(lambda _c: None), b.on_end(done)))
    e.change({"key": "a", "change": 1, "from": 0, "to": 1})
    ws = e.blob(100)
    ws.write(b"x" * 100)
    ws.end()
    e.finalize()
    wire = e.read()

    # feed everything but the blob's last 30 payload bytes
    d.write(wire[:-30])
    ck = d.checkpoint()
    assert ck.wire_offset == len(wire) - 30
    assert ck.frame == 1          # the change delivered; blob still open
    assert ck.row == 1
    assert ck.blob_offset == 70   # mid-blob cursor
    d.write(wire[-30:])
    d.end()
    assert d.finished
    ck2 = d.checkpoint()
    assert ck2.wire_offset == len(wire) and ck2.frame == 2
    assert ck2.blob_offset == 0


def test_checkpoint_roundtrips_through_dict():
    ck = SessionCheckpoint(wire_offset=7, frame=2, row=1, blob_offset=3,
                           digest={"change_seq": 1, "blob_seq": 0})
    assert SessionCheckpoint.from_dict(ck.as_dict()) == ck


def test_tpu_checkpoint_carries_digest_seq_state():
    d = protocol.decode(backend="tpu")
    d.on_digest(lambda *a: None)
    from dat_replication_protocol_tpu.wire.change_codec import encode_change
    from dat_replication_protocol_tpu.wire.framing import TYPE_CHANGE, frame

    d.write(frame(TYPE_CHANGE, encode_change(
        {"key": "k", "change": 1, "from": 0, "to": 1})))
    assert d.checkpoint().digest == {"change_seq": 1, "blob_seq": 0}


# -- WireJournal ------------------------------------------------------------

def test_journal_window_ack_and_read_from():
    j = WireJournal()
    j.append(b"abcdef")
    j.append(b"ghij")
    assert (j.start, j.end) == (0, 10)
    assert j.read_from(4) == b"efghij"
    assert j.read_from(10) == b""
    j.ack(6)
    assert (j.start, j.end) == (6, 10)
    assert j.read_from(6) == b"ghij"
    with pytest.raises(ResumeError) as ei:
        j.read_from(3)  # acked past: the window is gone
    assert ei.value.offset == 3
    with pytest.raises(ResumeError):
        j.read_from(11)  # ahead of production
    with pytest.raises(ValueError):
        j.ack(99)


def test_journal_trim_is_min_offset_aware_across_readers():
    """Regression (ISSUE 9 satellite): the original ack-trim assumed a
    single reader — with two attached cursors, one reader's ack must
    not trim the other reader's unread window."""
    j = WireJournal()
    j.append(b"0123456789")
    j.attach_reader("fast", 0)
    j.attach_reader("slow", 0)
    j.ack(8, reader="fast")
    # the slow reader still pins the window: nothing trimmed
    assert (j.start, j.end) == (0, 10)
    assert j.read_from(0) == b"0123456789"
    j.ack(5, reader="slow")
    assert (j.start, j.end) == (5, 10)  # trimmed to the MINIMUM ack
    # a bare (reader-less) ack is floored by the slowest reader too
    j.ack(9)
    assert j.start == 5
    # a departed laggard releases its pin on the next ack
    j.detach_reader("slow")
    j.ack(8, reader="fast")
    assert j.start == 8
    with pytest.raises(ValueError):
        j.ack(99, reader="fast")  # beyond production
    with pytest.raises(ValueError):
        j.ack(99)  # a bare over-end ack is a caller bug on EVERY
        # path — the reader floor must not silently mask it
    with pytest.raises(ValueError):
        j.ack(9, reader="ghost")  # unknown cursor


def test_journal_second_cursor_past_trim_point_is_structured():
    """Regression (ISSUE 9 satellite): attaching a cursor below the
    trimmed window must raise ResumeError carrying the retained range
    in the message — not silently short-read from the wrong place."""
    j = WireJournal()
    j.append(b"x" * 100)
    j.attach_reader("r1", 0)
    j.ack(60, reader="r1")  # sole reader: trims to 60
    assert j.start == 60
    with pytest.raises(ResumeError) as ei:
        j.attach_reader("r2", 40)  # past the trim point
    assert ei.value.offset == 40
    assert "[60, 100)" in str(ei.value)  # the retained range, in-message
    with pytest.raises(ResumeError) as ei:
        j.read_from(40)
    assert "[60, 100)" in str(ei.value)
    with pytest.raises(ResumeError):
        j.attach_reader("r3", 101)  # ahead of production
    # attaching INSIDE the retained range still works
    j.attach_reader("ok", 70)
    assert j.read_from(70) == b"x" * 30


def test_encoder_journal_tee_is_byte_exact_and_order_preserving():
    e = protocol.encode()
    j = WireJournal()
    e.attach_journal(j)
    e.change({"key": "a", "change": 1, "from": 0, "to": 1, "value": b"v"})
    ws = e.blob(5)
    ws.write(b"12")
    ws.end(b"345")
    e.finalize()
    parts = []
    while True:
        d = e.read(7)  # odd chunk size: bytes cross read boundaries
        if d is None:
            break
        parts.append(d)
    assert j.read_from(0) == b"".join(parts)
    assert j.end == e.bytes


# -- BackoffPolicy ----------------------------------------------------------

def test_backoff_full_jitter_is_bounded_and_seeded():
    p1 = BackoffPolicy(base=0.1, cap=1.0, max_retries=9, seed=42)
    p2 = BackoffPolicy(base=0.1, cap=1.0, max_retries=9, seed=42)
    delays = [p1.delay(k) for k in range(1, 10)]
    assert delays == [p2.delay(k) for k in range(1, 10)]  # reproducible
    for k, d in enumerate(delays, start=1):
        assert 0.0 <= d <= min(1.0, 0.1 * 2 ** k)  # full-jitter envelope
    assert max(delays) <= 1.0  # cap honored at high attempt counts


def test_retrying_bounded_attempts_then_structured_error():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        raise OSError("nope")

    policy = BackoffPolicy(base=0.01, max_retries=3, seed=0,
                           sleep=slept.append)
    with pytest.raises(ProtocolError) as ei:
        retrying(flaky, policy, describe="dial")
    assert calls["n"] == 4  # initial + 3 retries
    assert len(slept) == 3
    assert "dial failed after 4 attempt(s)" in str(ei.value)
    assert isinstance(ei.value.cause, OSError)


def test_retrying_recovers_midway():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("warming up")
        return "ok"

    policy = BackoffPolicy(base=0, max_retries=5, seed=0)
    assert retrying(flaky, policy) == "ok"
    assert calls["n"] == 3


# -- structured ProtocolError ------------------------------------------------

def test_protocol_error_context_renders_and_is_introspectable():
    cause = OSError("link down")
    err = ProtocolError("session lost", frame=7, offset=4242, cause=cause)
    assert err.frame == 7 and err.offset == 4242 and err.cause is cause
    s = str(err)
    assert "frame=7" in s and "byte=4242" in s and "link down" in s
    # bare form unchanged
    assert str(ProtocolError("plain")) == "plain"


def test_decoder_errors_carry_frame_and_byte_context():
    from dat_replication_protocol_tpu.wire.change_codec import encode_change
    from dat_replication_protocol_tpu.wire.framing import TYPE_CHANGE, frame

    d = protocol.decode()
    errs = []
    d.on_error(errs.append)
    d.write(frame(TYPE_CHANGE, encode_change(
        {"key": "k", "change": 1, "from": 0, "to": 1})))  # one good change
    d.write(b"\x05\x07xxxx")  # unknown type id 7
    assert d.destroyed
    (err,) = errs
    assert isinstance(err, ProtocolError)
    assert err.frame == 1  # one frame delivered before the bad one
    assert err.offset is not None and err.offset > 0


# -- transport: drain watcher (the lost-wakeup fix) --------------------------

def test_recv_over_wakes_immediately_on_cross_thread_ack():
    """The old pump polled every 50ms; the drain watcher must wake it
    as soon as the ack lands.  We hold the decoder's first-change ack,
    release it from another thread, and require end-to-end completion
    far faster than one poll period would allow if wakeups were lost."""
    e, d = protocol.encode(), protocol.decode()
    acks = []
    got = []
    d.change(lambda c, done: (got.append(c.key), acks.append(done)))

    for i in range(3):
        e.change({"key": f"k{i}", "change": i, "from": i, "to": i + 1})
    e.finalize()
    wire = e.read()

    def release():
        # ack each change ~5ms after it arrives, from OUR thread — every
        # wakeup crosses threads
        deadline = time.monotonic() + 10
        while not d.finished and time.monotonic() < deadline:
            if acks:
                acks.pop(0)()
            time.sleep(0.005)

    t = threading.Thread(target=release, daemon=True)
    t.start()
    t0 = time.monotonic()
    transport.recv_over(d, _mk_reader(wire), chunk_size=4096)
    elapsed = time.monotonic() - t0
    t.join(5)
    assert d.finished and got == ["k0", "k1", "k2"]
    # 3 cross-thread acks at ~5ms spacing: event-driven completes in
    # tens of ms.  The bound sits BELOW one WAKE_FALLBACK period (0.5s)
    # on purpose — with the watcher disabled, every stall costs a full
    # fallback poll and this fails (verified), so a regression that
    # silently breaks the event-driven wakeup cannot ship green
    assert elapsed < 0.4


def _mk_reader(data: bytes):
    from dat_replication_protocol_tpu.session.faults import bytes_reader

    return bytes_reader(data)


def test_decoder_drain_watcher_add_remove():
    d = protocol.decode()
    hits = []
    d._add_drain_watcher(lambda: hits.append(1))
    d.destroy()
    assert hits  # destroy wakes watchers
    d2 = protocol.decode()
    cb = lambda: hits.append(2)  # noqa: E731
    d2._add_drain_watcher(cb)
    d2._remove_drain_watcher(cb)
    d2._remove_drain_watcher(cb)  # double-remove is a no-op
    d2.destroy()
    assert hits == [1]


# -- transport: fd close-once guard -----------------------------------------

def test_send_over_fd_closes_exactly_once_and_guard_is_shareable():
    e = protocol.encode()
    e.change({"key": "k", "change": 1, "from": 0, "to": 1})
    e.finalize()
    r, w = os.pipe()
    closed = []
    real_close = os.close

    guard = transport.once(lambda: (closed.append(w), real_close(w)))
    got = []
    reader = threading.Thread(
        target=lambda: got.append(_read_all(r)), daemon=True)
    reader.start()
    returned = transport.send_over_fd(e, w, close=guard)
    assert returned is guard
    # the caller's own error-path cleanup calls the guard again: no
    # EBADF, no double close of a possibly-reused fd number
    guard()
    guard()
    assert closed == [w]
    reader.join(5)
    assert not reader.is_alive()  # the close delivered EOF to the peer
    os.close(r)
    assert got and len(got[0]) == e.bytes


def _read_all(fd: int):
    chunks = []
    while True:
        b = os.read(fd, 4096)
        if not b:
            return b"".join(chunks)
        chunks.append(b)


def test_once_guard_is_thread_safe():
    ran = []
    guard = transport.once(lambda: ran.append(1))
    ts = [threading.Thread(target=guard) for _ in range(16)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(5)
    assert ran == [1]


# -- sidecar: retry flags ----------------------------------------------------

def test_sidecar_bind_retries_through_transient_eaddrinuse():
    import socket as socket_mod

    from dat_replication_protocol_tpu import sidecar

    blocker = socket_mod.socket()
    blocker.bind(("127.0.0.1", 0))
    port = blocker.getsockname()[1]
    # no SO_REUSEADDR on the blocker + no listen: bind on the same port
    # fails while it lives; release it from a timer mid-retry
    threading.Timer(0.15, blocker.close).start()
    ready = threading.Event()
    policy = BackoffPolicy(base=0.1, cap=0.2, max_retries=10, seed=1)

    def serve():
        sidecar.serve_tcp("127.0.0.1", port, max_sessions=0,
                          ready_cb=lambda p: ready.set(),
                          retry_policy=policy)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    assert ready.wait(10), "bind never succeeded after the blocker left"
    t.join(10)
    assert not t.is_alive()


def test_sidecar_bind_gives_up_with_structured_error():
    import socket as socket_mod

    from dat_replication_protocol_tpu import sidecar

    blocker = socket_mod.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    port = blocker.getsockname()[1]
    try:
        policy = BackoffPolicy(base=0.001, cap=0.002, max_retries=2, seed=1)
        with pytest.raises(ProtocolError) as ei:
            sidecar.serve_tcp("127.0.0.1", port, max_sessions=0,
                              retry_policy=policy)
        assert "bind" in str(ei.value) and isinstance(ei.value.cause, OSError)
    finally:
        blocker.close()


def test_sidecar_cli_accepts_retry_flags(capsys):
    from dat_replication_protocol_tpu import sidecar

    with pytest.raises(SystemExit):
        sidecar.main(["--stdio", "--max-retries", "bad"])
    # flags parse and reach the policy: exercised via --help text
    with pytest.raises(SystemExit):
        sidecar.main(["--help"])
    out = capsys.readouterr().out
    assert "--max-retries" in out and "--backoff-base" in out


# -- asyncio face ------------------------------------------------------------

def test_open_connection_with_retry_dials_until_server_appears():
    async def main():
        import socket as socket_mod

        probe = socket_mod.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()  # port now free — and nothing listens yet

        server_box = {}

        async def start_server_later():
            await asyncio.sleep(0.1)
            # close the accepted writer: Python 3.12's wait_closed()
            # waits for every connection the server still holds
            server_box["srv"] = await asyncio.start_server(
                lambda r, w: w.close(), "127.0.0.1", port)

        starter = asyncio.ensure_future(start_server_later())
        policy = BackoffPolicy(base=0.05, cap=0.1, max_retries=20, seed=3)
        reader, writer = await open_connection_with_retry(
            "127.0.0.1", port, policy)
        writer.close()
        await starter
        server_box["srv"].close()
        await server_box["srv"].wait_closed()

    asyncio.run(asyncio.wait_for(main(), 30))


def test_open_connection_with_retry_exhausts_to_structured_error():
    async def main():
        import socket as socket_mod

        probe = socket_mod.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        policy = BackoffPolicy(base=0.001, cap=0.002, max_retries=2, seed=0)
        with pytest.raises(ProtocolError) as ei:
            await open_connection_with_retry("127.0.0.1", port, policy)
        assert "failed after 3 attempt(s)" in str(ei.value)
        assert isinstance(ei.value.cause, OSError)

    asyncio.run(asyncio.wait_for(main(), 30))


def test_send_over_async_stall_timeout_fails_structured():
    """A peer that never reads must fail the sender with a structured
    error within stall_timeout — not park the task forever."""
    async def main():
        import socket as socket_mod

        a, b = socket_mod.socketpair()
        a.setblocking(False)
        b.setblocking(False)
        # shrink the window so a modest payload wedges drain
        a.setsockopt(socket_mod.SOL_SOCKET, socket_mod.SO_SNDBUF, 8192)
        _, writer = await asyncio.open_connection(sock=a)
        writer.transport.set_write_buffer_limits(high=4096, low=1024)
        e = protocol.encode()
        errs = []
        e.on_error(errs.append)
        ws = e.blob(1 << 20)
        ws.write(b"x" * (1 << 20))
        ws.end()
        e.finalize()
        await asyncio.wait_for(
            send_over_async(e, writer, stall_timeout=0.3), 20)
        assert e.destroyed
        assert any(isinstance(x, ProtocolError) and "stalled" in str(x)
                   for x in errs)
        writer.transport.abort()
        writer.close()
        for s in (a, b):
            s.close()

    asyncio.run(asyncio.wait_for(main(), 30))


# -- FaultyWriter ------------------------------------------------------------

def test_faulty_writer_resegments_flips_and_drops():
    from dat_replication_protocol_tpu.session.faults import (
        FaultPlan,
        FaultyWriter,
        TransportFault,
    )

    sink = []
    w = FaultyWriter(sink.append, FaultPlan(seed=1, max_segment=3,
                                            flip_at=4, flip_mask=0x01))
    w.write(b"\x00" * 10)
    out = b"".join(sink)
    assert len(out) == 10 and max(len(c) for c in sink) <= 3
    assert out[4] == 0x01 and out.count(0) == 9  # exactly one byte flipped

    dead = FaultyWriter(sink.append, FaultPlan(seed=2, drop_at=5))
    with pytest.raises(TransportFault) as ei:
        dead.write(b"x" * 16)
    assert ei.value.offset == 5
    with pytest.raises(TransportFault):
        dead.write(b"more")  # the connection stays dead


# -- review fixes ------------------------------------------------------------

def test_run_resumable_retries_plain_oserror_from_real_sockets():
    """A source backed by a real socket raises ConnectionResetError (not
    TransportFault); the driver must take the reconnect path for it."""
    from dat_replication_protocol_tpu.session.faults import bytes_reader
    from dat_replication_protocol_tpu.session.reconnect import run_resumable

    e = protocol.encode()
    e.change({"key": "k", "change": 1, "from": 0, "to": 1})
    e.finalize()
    wire = e.read()

    class ResettingReader:
        def __init__(self, data, die):
            self._read = bytes_reader(data)
            self._die = die
            self._delivered = 0

        def read(self, n):
            if self._die and self._delivered >= 4:
                raise ConnectionResetError("peer reset")
            out = self._read(min(n, 4))
            self._delivered += len(out)
            return out

    def source(ckpt, failures):
        return ResettingReader(wire[ckpt.wire_offset:], die=(failures == 0))

    d = protocol.decode()
    got = []
    d.change(lambda c, done: (got.append(c.key), done()))
    stats = run_resumable(source, d,
                          BackoffPolicy(base=0.0001, max_retries=2, seed=0),
                          expected_total=len(wire), stall_timeout=5)
    assert stats["reconnects"] == 1 and "peer reset" in stats["faults"][0]
    assert got == ["k"] and d.finished


def test_attach_journal_after_reads_aligns_absolute_offsets():
    e = protocol.encode()
    e.change({"key": "early", "change": 1, "from": 0, "to": 1})
    head = e.read()  # emitted BEFORE the journal attaches
    j = WireJournal()
    e.attach_journal(j)
    assert j.start == len(head)  # window starts past the lost bytes
    e.change({"key": "late", "change": 2, "from": 1, "to": 2})
    e.finalize()
    tail = e.read()
    assert j.read_from(len(head)) == tail  # absolute offsets line up
    with pytest.raises(ResumeError):
        j.read_from(0)  # pre-attach bytes are honestly unrecoverable

    # a journal that cannot seek refuses a late attach instead of
    # silently misaligning
    e2 = protocol.encode()
    e2.change({"key": "x", "change": 1, "from": 0, "to": 1})
    e2.read()
    with pytest.raises(RuntimeError, match="cannot seek"):
        e2.attach_journal([])  # bare list: append() but no seek()


def test_app_handler_oserror_is_not_a_transport_fault():
    """An app callback raising OSError during delivery (ENOSPC while
    materializing a blob, say) must surface raw — retrying it as a
    'transport fault' would resume a stream the failed delivery
    desynchronized and bury the app's real error."""
    from dat_replication_protocol_tpu.session.faults import bytes_reader
    from dat_replication_protocol_tpu.session.reconnect import run_resumable

    e = protocol.encode()
    for i in range(3):
        e.change({"key": f"k{i}", "change": i, "from": i, "to": i + 1})
    e.finalize()
    wire = e.read()

    class R:
        def __init__(self, data):
            self._read = bytes_reader(data)

        def read(self, n):
            return self._read(n)

    d = protocol.decode()
    d.change(lambda c, done: (_ for _ in ()).throw(OSError("ENOSPC: disk full")))
    attempts = []

    def source(ckpt, failures):
        attempts.append(failures)
        return R(wire[ckpt.wire_offset:])

    with pytest.raises(OSError, match="ENOSPC"):
        run_resumable(source, d,
                      BackoffPolicy(base=0.0001, max_retries=5, seed=0),
                      expected_total=len(wire), stall_timeout=5)
    assert attempts == [0]  # no reconnect was attempted for an app error
