"""ReplicationHub unit layer (ISSUE 8): admission, QoS, telemetry.

The chaos isolation proof lives in tests/test_hub_faults.py; this file
pins the mechanisms it relies on — structured admission rejection,
per-session windows, weighted-fair batch composition, shedding policy,
the flush barrier, and the per-session telemetry/collector plumbing the
oracle cross-checks.
"""

import hashlib
import threading
import time

import pytest

import dat_replication_protocol_tpu as protocol
from dat_replication_protocol_tpu.hub import (
    HubBusy,
    HubError,
    ReplicationHub,
    SessionShed,
)

HARD_TIMEOUT = 30.0


def _h(p: bytes) -> bytes:
    return hashlib.blake2b(p, digest_size=32).digest()


def _hashlib_batch(payloads):
    return [_h(p) for p in payloads]


def _join_all(threads, timeout=HARD_TIMEOUT):
    for t in threads:
        t.join(timeout)
    assert all(not t.is_alive() for t in threads), "HANG"


# -- registration / admission -------------------------------------------------


def test_register_rejects_structured_when_session_cap_hit():
    with ReplicationHub(hash_batch=_hashlib_batch, max_sessions=2) as hub:
        a = hub.register("a")
        b = hub.register("b")
        with pytest.raises(HubBusy) as ei:
            hub.register("c")
        e = ei.value
        assert e.sessions == 2 and e.max_sessions == 2
        assert e.parked_bytes == 0 and e.parked_budget == hub.parked_budget
        a.close()
        # a released slot admits again — bounded state, not a latch
        c = hub.register("c")
        b.close()
        c.close()


def test_register_rejects_on_parked_budget(obs_enabled):
    from dat_replication_protocol_tpu.obs.events import EVENTS

    gate = threading.Event()

    def stuck_hash(payloads):
        gate.wait(HARD_TIMEOUT)
        return _hashlib_batch(payloads)

    hub = ReplicationHub(hash_batch=stuck_hash, parked_budget=500,
                         linger_s=0.0)
    try:
        s = hub.register("parker")
        # 300 parked bytes: past the admission threshold (budget // 2 —
        # admission closes BEFORE the shed cliff) but under the shed
        # budget itself, so the parked session survives while the
        # newcomer is refused.  submit() accounts synchronously, so no
        # settling wait is needed.
        s.submit(b"x" * 300, lambda d: None)
        with pytest.raises(HubBusy) as ei:
            hub.register("late")
        assert ei.value.parked_bytes >= 250
        rejects = EVENTS.events("hub.reject")
        assert rejects and rejects[-1]["fields"]["key"] == "late"
        assert obs_enabled.REGISTRY.counter("hub.rejected").value >= 1
    finally:
        gate.set()
        hub.close()


def test_duplicate_key_raises():
    with ReplicationHub(hash_batch=_hashlib_batch) as hub:
        s = hub.register("dup")
        with pytest.raises(ValueError):
            hub.register("dup")
        s.close()


# -- cross-session coalescing + correctness -----------------------------------


def test_many_sessions_coalesce_and_route_by_key():
    """N concurrent TpuDecoder sessions share ONE pipeline; every
    session's digest stream must be exactly its own (values pinned
    against hashlib), and the work must actually coalesce (fewer
    dispatched batches than total items)."""
    batches = []

    def recording_hash(payloads):
        batches.append(len(payloads))
        return _hashlib_batch(payloads)

    n_sessions, n_changes = 6, 40
    hub = ReplicationHub(hash_batch=recording_hash, linger_s=0.005)
    out: dict = {}

    def run_one(i):
        s = hub.register(f"k{i}")
        dec = protocol.decode(backend="tpu", pipeline=s)
        digs = []
        dec.on_digest(lambda kind, seq, d: digs.append((kind, seq, d)))
        e = protocol.encode()
        for j in range(n_changes):
            e.change({"key": f"s{i}-{j}", "change": j, "from": 0, "to": 1,
                      "value": b"v%d-%d" % (i, j)})
        b = e.blob(7)
        b.write(b"blob-%02d" % i)
        b.end()
        e.finalize()
        wire = b"".join(iter(lambda: e.read(4096) or b"", b""))
        for off in range(0, len(wire), 257):
            dec.write(wire[off:off + 257])
        dec.end()
        assert dec.finished
        out[i] = digs
        s.close()

    threads = [threading.Thread(target=run_one, args=(i,))
               for i in range(n_sessions)]
    for t in threads:
        t.start()
    _join_all(threads)
    hub.close()
    for i in range(n_sessions):
        digs = out[i]
        assert len(digs) == n_changes + 1
        # per-kind seqs are 0..n in order — delivery order preserved
        assert [s for k, s, _ in digs if k == "change"] == \
            list(range(n_changes))
        # values are THIS session's payload hashes, not a neighbor's
        from dat_replication_protocol_tpu.wire.change_codec import (
            encode_change,
        )

        for kind, seq, d in digs:
            if kind == "change":
                payload = encode_change({
                    "key": f"s{i}-{seq}", "change": seq, "from": 0,
                    "to": 1, "value": b"v%d-%d" % (i, seq),
                    "subset": None})
                assert d == _h(payload), (i, seq)
            else:
                assert d == _h(b"blob-%02d" % i)
    # coalescing happened: strictly fewer batches than items
    total_items = n_sessions * (n_changes + 1)
    assert sum(batches) == total_items
    assert len(batches) < total_items


def _wedged_hub(max_batch=16):
    """A hub whose dispatcher is deterministically parked inside its
    first device turn (one priming item), so tests can fill queues and
    call the composer directly without racing it."""
    entered = threading.Event()
    release = threading.Event()

    def gated_hash(payloads):
        entered.set()
        release.wait(HARD_TIMEOUT)
        return _hashlib_batch(payloads)

    hub = ReplicationHub(hash_batch=gated_hash, max_batch=max_batch,
                         linger_s=0.0)
    primer = hub.register("primer")
    primer.submit(b"prime", lambda d: None)
    assert entered.wait(5), "dispatcher never took the priming batch"
    return hub, release


def test_weighted_fair_batching_respects_weights():
    """With both queues saturated, one composed batch's per-session
    shares track the 3:1 weight ratio (quota pass), and spare budget is
    greedily filled (work-conserving)."""
    hub, release = _wedged_hub(max_batch=16)
    heavy = hub.register("heavy", weight=3.0)
    light = hub.register("light", weight=1.0)
    try:
        for i in range(40):
            heavy.submit(b"H" * 8, lambda d: None)
        for i in range(40):
            light.submit(b"L" * 8, lambda d: None)
        with hub._lock:
            batch = hub._compose_locked()
        by_key = {}
        for st, kind, item, cb, tag, nb in batch:
            by_key[st.key] = by_key.get(st.key, 0) + 1
        assert sum(by_key.values()) == 16
        # quota pass: 16 * 3/4 = 12 vs 16 * 1/4 = 4
        assert by_key["heavy"] == 12 and by_key["light"] == 4
    finally:
        release.set()
        hub.close()


def test_greedy_fill_is_work_conserving():
    hub, release = _wedged_hub(max_batch=16)
    heavy = hub.register("heavy", weight=3.0)
    light = hub.register("light", weight=1.0)
    try:
        for i in range(3):  # heavy has almost nothing queued
            heavy.submit(b"H", lambda d: None)
        for i in range(40):
            light.submit(b"L", lambda d: None)
        with hub._lock:
            batch = hub._compose_locked()
        by_key = {}
        for st, *_ in batch:
            by_key[st.key] = by_key.get(st.key, 0) + 1
        # light's surplus fills heavy's unused quota: full batch anyway
        assert sum(by_key.values()) == 16
        assert by_key == {"heavy": 3, "light": 13}
    finally:
        release.set()
        hub.close()


# -- windows / backpressure ---------------------------------------------------


def test_slow_consumer_stalls_only_its_own_window():
    """A session that submits without draining fills ITS window and its
    submit blocks; a co-resident session keeps completing unimpeded —
    the per-session QoS contract at the unit level."""
    hub = ReplicationHub(hash_batch=_hashlib_batch, window_items=8,
                         linger_s=0.0)
    slow = hub.register("slow")
    fast = hub.register("fast")
    fast_done = []
    blocked = threading.Event()
    proceed = threading.Event()

    def slow_run():
        # 8 fills the window; the 9th must park until completions drain
        # (which submit() does on entry) — park detection via timing
        for i in range(20):
            slow.submit(b"s" * 10, lambda d: proceed.wait(5))
            # the FIRST delivered completion parks inside the callback,
            # so the submit loop wedges behind its own consumer
            if i == 0:
                blocked.set()

    t_slow = threading.Thread(target=slow_run, daemon=True)
    t_slow.start()
    assert blocked.wait(5)

    def fast_run():
        for i in range(50):
            fast.submit(b"f%03d" % i, lambda d: fast_done.append(d))
        fast.flush()

    t_fast = threading.Thread(target=fast_run)
    t_fast.start()
    _join_all([t_fast], timeout=10)
    assert len(fast_done) == 50  # fast finished while slow sat parked
    proceed.set()
    _join_all([t_slow], timeout=10)
    slow.close()
    fast.close()
    hub.close()


def test_flush_is_a_per_session_barrier():
    hub = ReplicationHub(hash_batch=_hashlib_batch, linger_s=0.005)
    s = hub.register("flusher")
    got = []
    for i in range(100):
        s.submit(b"p%03d" % i, lambda d: got.append(d))
    s.flush()
    assert len(got) == 100
    assert got[7] == _h(b"p007")  # submit order preserved
    s.close()
    hub.close()


# -- shedding -----------------------------------------------------------------


def test_heaviest_offender_is_shed_first_and_neighbors_survive(obs_enabled):
    from dat_replication_protocol_tpu.obs.events import EVENTS

    release = threading.Event()

    def gated_hash(payloads):
        release.wait(HARD_TIMEOUT)
        return _hashlib_batch(payloads)

    hub = ReplicationHub(hash_batch=gated_hash, parked_budget=5_000,
                         window_items=10_000, window_bytes=10 << 20,
                         linger_s=0.0)
    flood = hub.register("flood")
    light = hub.register("light")
    light_got = []
    shed_seen = []

    def flood_run():
        try:
            for i in range(1000):
                flood.submit(b"x" * 100, lambda d: None)
        except SessionShed as e:
            shed_seen.append(e)

    t = threading.Thread(target=flood_run)
    t.start()
    _join_all([t], timeout=10)
    assert shed_seen, "over-budget flood was never shed"
    e = shed_seen[0]
    assert e.key == "flood" and e.reason == "parked-budget"
    assert e.parked_bytes > 5_000
    release.set()

    def light_run():
        for i in range(10):
            light.submit(b"y" * 10, lambda d: light_got.append(d))
        light.flush()

    t2 = threading.Thread(target=light_run)
    t2.start()
    _join_all([t2], timeout=10)
    assert len(light_got) == 10  # the neighbor never noticed
    sheds = EVENTS.events("hub.shed")
    assert len(sheds) == 1
    assert sheds[0]["fields"]["key"] == "flood"
    assert sheds[0]["fields"]["reason"] == "parked-budget"
    assert obs_enabled.REGISTRY.counter("hub.shed").value == 1
    # further use of the shed session raises the same structured error
    with pytest.raises(SessionShed):
        flood.submit(b"more", lambda d: None)
    with pytest.raises(SessionShed):
        flood.flush()
    flood.close()
    light.close()
    hub.close()


def test_dispatch_latency_shed_arm(obs_enabled):
    """The secondary policy arm: a slow device turn plus parked bytes
    past half budget sheds the heaviest offender."""
    from dat_replication_protocol_tpu.obs.events import EVENTS

    def slow_hash(payloads):
        time.sleep(0.05)
        return _hashlib_batch(payloads)

    hub = ReplicationHub(hash_batch=slow_hash, parked_budget=10_000,
                         latency_shed_s=0.01, window_items=10_000,
                         linger_s=0.0, max_batch=8)
    s = hub.register("bursty")
    try:
        with pytest.raises(SessionShed) as ei:
            for i in range(200):
                s.submit(b"z" * 80, lambda d: None)
                time.sleep(0.001)
        assert ei.value.reason in ("dispatch-latency", "parked-budget")
        assert EVENTS.events("hub.shed")
    finally:
        s.close()
        hub.close()


# -- lifecycle / failure ------------------------------------------------------


def test_engine_failure_surfaces_as_hub_error_everywhere(obs_enabled):
    from dat_replication_protocol_tpu.obs.events import EVENTS

    def broken_hash(payloads):
        raise RuntimeError("engine on fire")

    hub = ReplicationHub(hash_batch=broken_hash, linger_s=0.0)
    s = hub.register("victim")
    with pytest.raises(HubError):
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            s.submit(b"x", lambda d: None)
            time.sleep(0.005)
        pytest.fail("dispatcher failure never surfaced")
    errs = EVENTS.events("hub.error")
    assert errs and "engine on fire" in errs[0]["fields"]["error"]
    with pytest.raises(HubError):  # registration fails too
        hub.register("late")
    hub.close()


def test_close_makes_sessions_raise_hub_error():
    hub = ReplicationHub(hash_batch=_hashlib_batch)
    s = hub.register("orphan")
    hub.close()
    with pytest.raises(HubError):
        s.submit(b"x", lambda d: None)


# -- per-session telemetry (ISSUE 8 satellite) --------------------------------


def test_hub_sessions_gauge_and_collector_entries(obs_enabled):
    hub = ReplicationHub(hash_batch=_hashlib_batch, linger_s=0.002)
    a = hub.register("alpha")
    b = hub.register("beta")
    got = []
    for i in range(12):
        a.submit(b"a" * 50, lambda d: got.append(d))
    a.flush()
    snap = obs_enabled.REGISTRY.snapshot()
    assert snap["gauges"]["hub.sessions"] == 2.0
    # labeled per-session entries ride the snapshot via the collector
    assert snap["counters"]["hub.session.submitted{session=alpha}"] == 12
    assert snap["counters"]["hub.session.delivered{session=alpha}"] == 12
    assert snap["counters"]["hub.session.submitted{session=beta}"] == 0
    assert snap["gauges"]["hub.session.parked_bytes{session=alpha}"] == 0.0
    assert snap["counters"]["hub.session.dispatches{session=alpha}"] >= 1
    # sessions_snapshot is the same story keyed for --stats-fd lines
    per = hub.sessions_snapshot()
    assert per["alpha"]["submitted"] == 12
    assert per["alpha"]["delivered"] == 12
    assert per["alpha"]["shed"] is None
    a.close()
    snap2 = obs_enabled.REGISTRY.snapshot()
    # dead sessions drop out of the breakdown (bounded cardinality)
    assert "hub.session.submitted{session=alpha}" not in snap2["counters"]
    assert snap2["gauges"]["hub.sessions"] == 1.0
    b.close()
    hub.close()


def test_labeled_collector_entries_render_as_prom_labels(obs_enabled):
    from dat_replication_protocol_tpu.obs import metrics

    hub = ReplicationHub(hash_batch=_hashlib_batch)
    s = hub.register("p1")
    text = metrics.to_prom_text()
    assert 'dat_hub_session_parked_bytes{session="p1"} 0' in text
    assert "# TYPE dat_hub_sessions gauge" in text
    s.close()
    hub.close()


def test_mesh_sharded_hub_engine_matches_hashlib(monkeypatch, obs_enabled):
    """The cross-session batch sharded over the 8-device virtual mesh
    (batch-dim NamedSharding): digests must be byte-identical to
    hashlib, routed back to the right sessions."""
    monkeypatch.setenv("DAT_DEVICE_HASH", "1")  # opt into the device path
    hub = ReplicationHub(mesh="auto", linger_s=0.01)
    assert hub.mesh_devices == 8
    a = hub.register("ma")
    b = hub.register("mb")
    got_a, got_b = [], []
    payloads_a = [b"mesh-a-%d" % i for i in range(10)]
    payloads_b = [b"mesh-b-%d" % i * 3 for i in range(7)]
    for p in payloads_a:
        a.submit(p, lambda d: got_a.append(d))
    for p in payloads_b:
        b.submit(p, lambda d: got_b.append(d))
    a.flush()
    b.flush()
    assert got_a == [_h(p) for p in payloads_a]
    assert got_b == [_h(p) for p in payloads_b]
    # the mesh engine counts its transfers like the single-device one
    counters = obs_enabled.snapshot()["counters"]
    assert counters["device.h2d.bytes"] > 0
    assert counters["device.d2h.bytes"] == 64 * 17
    a.close()
    b.close()
    hub.close()


def test_register_rejects_label_breaking_keys():
    # keys ride telemetry label sets and JSON breakdowns: structural
    # characters would corrupt the exposition for EVERY session
    with ReplicationHub(hash_batch=_hashlib_batch) as hub:
        for bad in ("a,b", "a{b", "a}b", 'a"b', "a=b", "a\nb", ""):
            with pytest.raises(ValueError):
                hub.register(bad)
        ok = hub.register("tenant-a:10.0.0.7:4711")  # sidecar shape
        ok.close()


def test_stale_hub_close_keeps_successor_collector(obs_enabled):
    # rolling restart: hub B starts while hub A drains; A closing late
    # must not delete B's live collector entries
    hub_a = ReplicationHub(hash_batch=_hashlib_batch)
    hub_b = ReplicationHub(hash_batch=_hashlib_batch)  # replaces A's
    s = hub_b.register("survivor")
    hub_a.close()
    snap = obs_enabled.REGISTRY.snapshot()
    assert "hub.session.submitted{session=survivor}" in snap["counters"]
    s.close()
    hub_b.close()
