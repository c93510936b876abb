"""The many-publishers hub deployment (ISSUE 33, configuration
`edgehub1g`) at a small size, through the normal path: one
`ReplicationHub` behind one `EdgeLoop`, as `sidecar --tcp --edge --hub
--hub-parked-budget B` builds them, 8 concurrent raw-wire client
sessions of 16 seeded blobs.  Every reply is held against the
benchmark's own plain reference (`benchmarks/reference/digests.py`:
hashlib and WIRE.md, nothing of the package).

The sizes keep the deployment's arithmetic.  There: 8 publishers x (a
32 MiB window + the 1 MiB blob that crosses it) = 264 MiB against the
default 256 MiB budget (admission closes at half) and the stated 1 GiB.
Here: a window of 15 blobs, so a session of 16 blobs IS a full window
plus one blob; the default's proportion is a budget of 8 windows, the
stated one four times that.
"""

import importlib.util
import os
import socket
import threading
import time

import numpy as np
import pytest

from dat_replication_protocol_tpu.edge import EdgeLoop
from dat_replication_protocol_tpu.hub import ReplicationHub

_REF = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "reference", "digests.py")
_spec = importlib.util.spec_from_file_location("plain_reference", _REF)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

PUBLISHERS = 8
BLOBS = 16
BLOB = 64 << 10
WINDOW = (BLOBS - 1) * BLOB               # as 32 MiB is to a 1 MiB blob
SESSION = BLOBS * BLOB                    # a full window plus one blob
# what a session's parked blobs pin of the slabs they arrived in (ISSUE
# 34): themselves and the frame headers between them, 4 bytes each
PINNED = SESSION + BLOBS * len(ref.frame_header(BLOB, ref.TYPE_BLOB))
DEFAULT_BUDGET = 8 * WINDOW               # as 256 MiB is to 32 MiB
STATED_BUDGET = 4 * DEFAULT_BUDGET        # as 1 GiB is to 256 MiB
HARD_TIMEOUT = 60


def _session(seed: int, client: int):
    """One publisher's wire bytes and the reply stream it must get."""
    rng = np.random.default_rng([seed, 33, client])
    blobs = [rng.bytes(BLOB) for _ in range(BLOBS)]
    wire = b"".join(ref.frame_header(len(b), ref.TYPE_BLOB) + b
                    for b in blobs)
    return wire, [ref.digest(b) for b in blobs]


def _faults(reply: bytes, digests: list) -> list:
    """What is wrong with a whole reply stream: count, order per kind,
    bytes (the reference's own field-by-field comparison)."""
    out, pos, seq = [], 0, 0
    while pos < len(reply):
        got = ref.parse_frame(reply, pos)
        if got is None:
            out.append(f"reply ends inside frame {seq}")
            break
        frame_type, fields, pos = got
        if seq >= len(digests):
            out.append(f"a reply beyond item {seq - 1}")
            break
        fault = ref.reply_fault(frame_type, fields, "blob", seq,
                                digests[seq])
        if fault:
            out.append(fault)
        seq += 1
    if seq != len(digests):
        out.append(f"{seq} digest records for {len(digests)} items")
    return out


def _publish(port: int, wire: bytes, hold=None) -> bytes:
    """One client session: everything sent, the write side shut, the
    reply read to its end (EOF comes only after the last digest).  With
    `hold`, the second half waits, so that every session is live at
    once."""
    c = socket.create_connection(("127.0.0.1", port), timeout=10)
    c.settimeout(HARD_TIMEOUT)
    try:
        half = len(wire) // 2 if hold is not None else len(wire)
        c.sendall(wire[:half])
        if hold is not None:
            hold.wait(HARD_TIMEOUT)
            c.sendall(wire[half:])
        c.shutdown(socket.SHUT_WR)
        parts = []
        while True:
            d = c.recv(1 << 16)
            if not d:
                return b"".join(parts)
            parts.append(d)
    finally:
        c.close()


def _serve(hub: ReplicationHub, sessions: int):
    loop = EdgeLoop(hub, max_sessions=sessions)
    port = loop.bind("127.0.0.1", 0)
    t = threading.Thread(target=loop.serve, daemon=True)
    t.start()
    return loop, port, t


def _start_publishers(port: int, traffic: list, replies: dict,
                      hold=None) -> list:
    """One thread a publisher, started; `replies[i]` is its reply."""
    def client(i):
        replies[i] = _publish(port, traffic[i][0], hold)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in (traffic if isinstance(traffic, dict)
                         else range(len(traffic)))]
    for th in threads:
        th.start()
    return threads


def _join_all(threads: list, loop_thread) -> None:
    for th in threads:
        th.join(HARD_TIMEOUT)
        assert not th.is_alive(), "a publisher hangs"
    loop_thread.join(timeout=10)


def _until(pred, what: str):
    deadline = time.monotonic() + HARD_TIMEOUT
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def test_the_budgets_keep_the_deployments_arithmetic():
    # 8 x (32 MiB + 1 MiB) = 264 MiB: over half of 256 MiB, under half
    # of 1 GiB; the same three lines at this test's sizes
    real = 8 * ((32 << 20) + (1 << 20))
    assert (256 << 20) // 2 <= real < (1 << 30) // 2
    assert (256 << 20, 1 << 30) == (8 * (32 << 20), 4 * (256 << 20))
    parked = PUBLISHERS * (WINDOW + BLOB)
    assert parked == PUBLISHERS * SESSION
    assert DEFAULT_BUDGET // 2 <= parked < STATED_BUDGET // 2


@pytest.mark.parametrize("seed", [33, 2147483659])
def test_eight_publishers_under_the_stated_budget(obs_enabled, seed):
    """The deployment: the default engine, 8 sessions live at once,
    nothing rejected, nothing shed, every guarantee held; and the two
    instruments the deployment brought."""
    hub = ReplicationHub(parked_budget=STATED_BUDGET, window_bytes=WINDOW)
    loop, port, t = _serve(hub, PUBLISHERS)
    traffic = [_session(seed, i) for i in range(PUBLISHERS)]
    replies: dict = {}
    hold = threading.Event()
    sampled: list = []
    done = threading.Event()

    def sample():
        while not done.is_set():
            sampled.append(
                obs_enabled.REGISTRY.gauge("hub.parked.bytes").value)
            time.sleep(0.001)

    sampler = threading.Thread(target=sample, daemon=True)
    try:
        sampler.start()
        threads = _start_publishers(port, traffic, replies, hold)
        _until(lambda: loop.snapshot()["sessions"] == PUBLISHERS,
               "all 8 sessions live at once")
        assert hub.admission_state()["open"] is True
        hold.set()
        _join_all(threads, t)
        done.set()
        sampler.join(5)
        snap = obs_enabled.snapshot()
    finally:
        hold.set()
        done.set()
        hub.close()
    for i in range(PUBLISHERS):
        assert _faults(replies[i], traffic[i][1]) == [], f"publisher {i}"
    counters, gauges = snap["counters"], snap["gauges"]
    assert counters["hub.admitted"] == PUBLISHERS
    assert counters["hub.rejected"] == 0 and counters["hub.shed"] == 0
    assert counters["hub.dispatch.items"] == PUBLISHERS * BLOBS
    # the high-water mark: over every sample of the gauge it tops, and
    # under the line where admission would have closed
    peak = gauges["hub.parked.peak_bytes"]
    assert gauges["hub.parked.budget_bytes"] == STATED_BUDGET
    assert sampled and peak >= max(sampled) and peak >= BLOB
    assert peak <= PUBLISHERS * PINNED < STATED_BUDGET // 2
    # one observation a composed batch, each of 1 to 8 sessions
    hist = snap["histograms"]["hub.dispatch.sessions"]
    assert hist["count"] == counters["hub.dispatch.batches"] > 0
    assert hist["count"] <= hist["sum"] <= PUBLISHERS * hist["count"]
    assert sum(n for le, n in hist["buckets"]
               if le == "+inf" or le > PUBLISHERS) == 0
    assert 1 <= hist["p50"] <= hist["p99"] <= PUBLISHERS


def _gated_hub(budget: int):
    """A hub whose engine waits at a gate: what the publishers send
    stays parked, so the budget's arithmetic can be read exactly."""
    gate = threading.Event()

    def gated_hash(payloads):
        gate.wait(HARD_TIMEOUT)
        return [ref.digest(bytes(p)) for p in payloads]

    return gate, ReplicationHub(hash_batch=gated_hash, parked_budget=budget,
                                window_bytes=WINDOW)


def test_every_window_full_at_once_fits_the_stated_budget(obs_enabled):
    """The worst case the configuration's `assumed` reckons with: all 8
    publishers hold a full window plus a blob while the device answers
    nothing.  Under the stated budget admission stays open."""
    gate, hub = _gated_hub(STATED_BUDGET)
    loop, port, t = _serve(hub, PUBLISHERS)
    traffic = [_session(7, i) for i in range(PUBLISHERS)]
    replies: dict = {}
    try:
        threads = _start_publishers(port, traffic, replies)
        # reads stop once a window is full: each session parks its
        # window and, where one read turn carried it, the 16th blob
        _until(lambda: hub.snapshot()["parked_bytes"] >= PUBLISHERS * WINDOW,
               "every publisher's window full")
        state = hub.admission_state()
        assert state["open"] is True and state["sessions"] == PUBLISHERS
        gate.set()
        _join_all(threads, t)
        snap = obs_enabled.snapshot()
    finally:
        gate.set()
        hub.close()
    for i in range(PUBLISHERS):
        assert _faults(replies[i], traffic[i][1]) == [], f"publisher {i}"
    assert PUBLISHERS * WINDOW <= snap["gauges"]["hub.parked.peak_bytes"] \
        <= PUBLISHERS * PINNED < STATED_BUDGET // 2
    assert snap["counters"]["hub.rejected"] == 0
    assert snap["counters"]["hub.shed"] == 0


def test_at_the_defaults_proportion_the_fifth_publisher_is_refused(
        obs_enabled):
    """Why the configuration states a budget: with the budget at the
    default's proportion (8 windows), four full windows close admission
    and the fifth publisher is refused (`HubBusy`: EOF, no reply byte),
    so `no session is rejected or shed` cannot hold there."""
    from dat_replication_protocol_tpu.obs.events import EVENTS

    gate, hub = _gated_hub(DEFAULT_BUDGET)
    loop, port, t = _serve(hub, 5)
    traffic = [_session(11, i) for i in range(5)]
    replies: dict = {}
    threads: list = []
    try:
        for n in range(1, 5):
            # one at a time: publisher n's window fills before n + 1 asks
            threads += _start_publishers(
                port, {n - 1: traffic[n - 1]}, replies)
            _until(lambda: hub.snapshot()["parked_bytes"] >= n * WINDOW,
                   f"publisher {n}'s window full")
            # three full windows leave admission open, the fourth shuts it
            assert hub.admission_state()["open"] is (n < 4)
        try:
            refused = _publish(port, traffic[4][0])
        except OSError:
            # closed with its megabyte unread: the kernel may answer
            # the send, the shutdown or the read with a reset
            # (ECONNRESET, EPIPE, ENOTCONN), which is no reply byte either
            refused = b""
        assert refused == b""
        _until(lambda: loop.admission_state()["rejected"] == 1,
               "the edge's count of the refusal")
        rejects = EVENTS.events("hub.reject")
        assert rejects and DEFAULT_BUDGET // 2 \
            <= rejects[-1]["fields"]["parked_bytes"] <= 4 * PINNED
        gate.set()
        _join_all(threads, t)
        snap = obs_enabled.snapshot()
    finally:
        gate.set()
        hub.close()
    # the four that were admitted lose nothing
    for i in range(4):
        assert _faults(replies[i], traffic[i][1]) == [], f"publisher {i}"
    assert snap["counters"]["hub.rejected"] == 1
    assert snap["counters"]["hub.admitted"] == 4
    assert snap["counters"]["hub.shed"] == 0
    assert 4 * WINDOW <= snap["gauges"]["hub.parked.peak_bytes"] \
        <= 4 * PINNED


def test_dark_the_instruments_observe_nothing():
    from dat_replication_protocol_tpu.obs import metrics

    was_on = metrics.OBS.on
    metrics.OBS.on = False
    metrics.REGISTRY.reset()
    hub = ReplicationHub(parked_budget=STATED_BUDGET, window_bytes=WINDOW)
    loop, port, t = _serve(hub, 2)
    traffic = [_session(5, i) for i in range(2)]
    replies: dict = {}
    try:
        _join_all(_start_publishers(port, traffic, replies), t)
        snap = metrics.snapshot()
        assert snap["histograms"]["hub.dispatch.sessions"]["count"] == 0
        assert snap["gauges"]["hub.parked.peak_bytes"] == 0
        assert snap["gauges"]["hub.parked.bytes"] == 0
        assert snap["counters"]["hub.dispatch.batches"] == 0
    finally:
        hub.close()
        metrics.OBS.on = was_on
        metrics.REGISTRY.reset()
    for i in range(2):
        assert _faults(replies[i], traffic[i][1]) == [], f"publisher {i}"
