"""Fleet observability plane (ISSUE 11): watermarks, endpoint, aggregator.

Layers under test:

* **Watermark exactness** — the exported cursors ARE the journal's and
  decoder's byte counts, not approximations: gauge == ``journal.end``,
  gauge == ``decoder._parsed``, at any instant.
* **The lag join** — ``append − parsed`` in bytes, clock-free seconds
  from the sender's marks ring (the aggregator never compares two
  machines' clocks).
* **The chaos oracle** (acceptance): a 20-seed sweep where a live
  sender outpaces a receiver running through the PR 2 fault injector —
  the aggregator's reported lag must match ground truth reconstructed
  from journal/decoder state at EVERY poll, rise while the fault holds
  the receiver back, fall after resume, and end at EXACTLY zero when
  the decoded session matches (plus a 100-seed slow soak).
* **The scrape endpoint** — all four routes, read-only-ness (a
  continuous scraper changes nothing and costs the hot path nothing
  measurable), the disabled-gate dark path, staged /healthz.
* **SLO gate** — ``fleet --check`` exit codes: pass, doctored-fail,
  malformed-SLO; this file IS the tier-1 live gate (the 2-replica
  in-process scenario runs un-slow-marked).
* **N-log timeline** — the offline mirror: 3-log golden merge clean,
  doctored gap flagged.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request

import pytest

import dat_replication_protocol_tpu as protocol
from dat_replication_protocol_tpu.obs.fleet import (
    FleetTarget,
    FleetView,
    evaluate_slo,
    load_slo,
    render_dashboard,
    run_fleet_check,
)
from dat_replication_protocol_tpu.obs.http import (
    ObsHttpServer,
    default_healthz,
    default_snapshot,
)
from dat_replication_protocol_tpu.obs.watermarks import WATERMARKS, link_lag
from dat_replication_protocol_tpu.session.faults import FaultPlan, FaultyReader
from dat_replication_protocol_tpu.session.reconnect import (
    BackoffPolicy,
    run_resumable,
)
from dat_replication_protocol_tpu.session.resume import WireJournal
from dat_replication_protocol_tpu.wire.framing import ProtocolError

HARD_TIMEOUT = 30.0


def _with_watchdog(fn):
    box: dict = {}

    def run():
        try:
            box["ret"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to the test
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(HARD_TIMEOUT)
    assert not t.is_alive(), f"HANG: still running after {HARD_TIMEOUT}s"
    if "err" in box:
        raise box["err"]
    return box["ret"]


def _build_wire(rows: int = 40) -> bytes:
    e = protocol.encode()
    j = WireJournal()
    e.attach_journal(j)
    for i in range(rows):
        e.change({"key": f"k-{i:04d}", "change": i, "from": i, "to": i + 1,
                  "value": b"v" * (i % 23)})
    b = e.blob(64)
    b.write(b"x" * 64)
    b.end()
    e.finalize()
    while e.read(4096) is not None:
        pass
    return j.read_from(0)


def _expected_events(wire: bytes) -> list:
    dec = protocol.decode()
    events: list = []
    dec.change(lambda c, done: (events.append(("change", c.key, c.value)),
                                done()))
    dec.blob(lambda b, done: b.collect(
        lambda data: (events.append(("blob", data)), done())))
    dec.write(wire)
    dec.end()
    assert dec.finished
    return events


class _Follower:
    """Blocking reader over a growing journal — the live-replication
    transport for the in-process fleet (reads block until the producer
    appends past the cursor or declares EOF)."""

    def __init__(self, journal: WireJournal, start: int,
                 done: threading.Event):
        self._j = journal
        self._pos = start
        self._done = done

    def read(self, n: int) -> bytes:
        while True:
            if self._j.end > self._pos:
                data = bytes(self._j.read_from(self._pos)[:n])
                self._pos += len(data)
                return data
            if self._done.is_set() and self._j.end <= self._pos:
                return b""
            time.sleep(0.0005)


# -- watermark exactness ------------------------------------------------------


def test_watermark_gauges_are_exactly_the_journal_byte_counts(obs_enabled):
    j = WireJournal()
    j.watermark("wm-x")
    j.append(b"a" * 100)
    j.append(b"b" * 55)
    j.attach_reader("r", 0)
    j.ack(60, reader="r")
    gauges = obs_enabled.REGISTRY.snapshot()["gauges"]
    assert gauges["session.wire.offset{link=wm-x,role=append}"] == 155.0
    assert gauges["session.wire.offset{link=wm-x,role=acked}"] == 60.0
    assert gauges["session.wire.offset{link=wm-x,role=append}"] == float(
        j.end)
    # marks recorded one per append, monotone offsets
    snap = WATERMARKS.snapshot()["links"]["wm-x"]
    assert [m[0] for m in snap["marks"]] == [100, 155]
    WATERMARKS.untrack("wm-x")
    assert "wm-x" not in WATERMARKS.snapshot()["links"]


def test_decoder_watermarks_track_parsed_and_checkpoint(obs_enabled):
    wire = _build_wire(8)
    dec = protocol.decode()
    dec.watermark("wm-d")
    dec.change(lambda c, done: done())
    dec.blob(lambda b, done: b.collect(lambda _d: done()))
    half = len(wire) // 2
    dec.write(wire[:half])
    snap = WATERMARKS.snapshot()["links"]["wm-d"]["offsets"]
    assert snap["accepted"] == dec.bytes == half
    assert snap["parsed"] == dec._parsed <= half
    assert snap["checkpoint"] == 0  # no checkpoint exported yet
    ckpt = dec.checkpoint()
    snap = WATERMARKS.snapshot()["links"]["wm-d"]["offsets"]
    assert snap["checkpoint"] == ckpt.wire_offset == half
    dec.write(wire[half:])
    dec.end()
    assert dec.finished
    snap = WATERMARKS.snapshot()["links"]["wm-d"]["offsets"]
    assert snap["parsed"] == snap["accepted"] == len(wire)
    WATERMARKS.untrack("wm-d")


def test_link_label_rejects_structural_characters(obs_enabled):
    for bad in ("", "a,b", "a=b", 'a"b', "a\nb", "{x}"):
        with pytest.raises(ValueError):
            WATERMARKS.track("append", bad, lambda: 0)
    with pytest.raises(ValueError):
        WATERMARKS.track("", "ok-link", lambda: 0)


def test_marks_only_link_is_a_clock_source_not_a_half_link(obs_enabled):
    """The fan-out shared publish ring is marks-only (no cursors): it
    must NOT export as a joinable link, or the SLO gate would fail a
    healthy fan-out fleet on a link that can never join (review
    regression)."""
    WATERMARKS.mark("wm-clock", 100)
    assert "wm-clock" not in WATERMARKS.snapshot()["links"]
    # ...but a per-peer link aliasing it still resolves its marks
    WATERMARKS.track("append", "wm-peer", lambda: 100,
                     marks_from="wm-clock")
    WATERMARKS.track("delivered", "wm-peer", lambda: 40)
    rec = WATERMARKS.snapshot()["links"]["wm-peer"]
    assert [m[0] for m in rec["marks"]] == [100]
    assert rec["lag_bytes"] == 60 and rec["lag_seconds"] is not None
    # the SLO gate sees only real links
    view = FleetView([default_snapshot])
    rows = evaluate_slo({"require_converged": True}, view.poll())
    assert {r["subject"] for r in rows} == {"wm-peer"}
    WATERMARKS.untrack("wm-peer")
    WATERMARKS.untrack("wm-clock")


def test_outrun_marks_ring_never_understates_age(obs_enabled):
    """When older marks were evicted and the first retained mark is
    already past the receive frontier, the true age is OLDER than
    anything attributable — the join must say unknown (None), never a
    too-young number an SLO bound would wrongly pass (review
    regression)."""
    marks = [(500, 11.0), (1000, 12.5)]
    # nothing dropped: first-mark attribution is exact
    assert link_lag({"append": 1000, "parsed": 100}, marks, 13.0,
                    marks_dropped=0)[1] == pytest.approx(2.0)
    # ring outrun: the frontier byte predates every retained mark
    assert link_lag({"append": 1000, "parsed": 100}, marks, 13.0,
                    marks_dropped=7)[1] is None
    # dropped marks but a retained predecessor covers the frontier:
    # still exact
    assert link_lag({"append": 1000, "parsed": 600}, marks, 13.0,
                    marks_dropped=7)[1] == pytest.approx(0.5)


def test_dying_cursor_goes_missing_not_fatal(obs_enabled):
    WATERMARKS.track("append", "wm-dead", lambda: 1 // 0)
    WATERMARKS.track("acked", "wm-dead", lambda: 7)
    offs = WATERMARKS.snapshot()["links"]["wm-dead"]["offsets"]
    assert offs == {"acked": 7}  # the raising cursor vanished, quietly
    WATERMARKS.untrack("wm-dead")


# -- the lag join -------------------------------------------------------------


def test_link_lag_join_bytes_and_clock_free_seconds():
    offsets = {"append": 1000, "parsed": 400}
    marks = [(300, 10.0), (500, 11.0), (1000, 12.5)]
    lag_b, lag_s = link_lag(offsets, marks, now=13.0)
    assert lag_b == 600
    # oldest unparsed byte: first mark past 400 is (500, 11.0) -> 2.0s
    assert lag_s == pytest.approx(2.0)
    assert link_lag({"append": 5, "parsed": 5}, marks, 13.0) == (0, 0.0)
    assert link_lag({"append": 5}, marks, 13.0) == (None, None)
    # behind but no covering mark: bytes exact, age honestly unknown
    assert link_lag({"append": 9, "parsed": 1}, [], 13.0) == (8, None)


def test_fleet_join_across_two_targets_uses_sender_clock():
    # sender and receiver snapshots come from DIFFERENT processes with
    # different monotonic bases — the join must use the sender's
    sender_snap = {"watermarks": {"monotonic": 107.0, "links": {
        "L": {"offsets": {"append": 900},
              "marks": [[450, 100.0], [900, 106.0]]}}}}
    receiver_snap = {"watermarks": {"monotonic": 55512.0, "links": {
        "L": {"offsets": {"parsed": 440}, "marks": []}}}}
    view = FleetView([FleetTarget(lambda: sender_snap, name="sender"),
                      FleetTarget(lambda: receiver_snap, name="receiver")])
    sample = view.poll()
    entry = sample["links"]["L"]
    assert entry["lag_bytes"] == 460
    # first mark past 440 is (450, t=100.0) on the sender clock 107.0
    assert entry["lag_seconds"] == pytest.approx(7.0)
    assert sorted(entry["targets"]) == ["receiver", "sender"]


def test_fleet_drain_rate_from_history_ring():
    lag = {"v": 1000}
    t0 = {"v": 0}

    def snap():
        return {"watermarks": {"monotonic": 1.0, "links": {
            "L": {"offsets": {"append": 1000, "parsed": 1000 - lag["v"]},
                  "marks": []}}}}

    view = FleetView([snap])
    view.poll()
    lag["v"] = 0
    time.sleep(0.05)
    sample = view.poll()
    assert sample["links"]["L"]["lag_bytes"] == 0
    assert sample["links"]["L"]["drain_bps"] > 0  # lag shrank -> draining
    assert len(view.history("L")) == 2


# -- chaos oracle (acceptance) ------------------------------------------------

_CHAOS_WIRE = _build_wire(40)
_CHAOS_EXPECTED = _expected_events(_CHAOS_WIRE)


def _chaos_seed(seed: int):
    """One live replication run under an injected fault: producer
    appends the prebuilt wire into a watermarked journal in timed
    chunks; the receiver follows through FaultyReader; the aggregator
    polls throughout.  Returns (samples, stats, events, journal, dec)."""
    wire = _CHAOS_WIRE
    scenario = ("stall", "truncate")[seed % 2]
    at = 64 + (seed * 97) % (len(wire) // 2)

    j = WireJournal()
    j.watermark("chaos")
    dec = protocol.decode()
    dec.watermark("chaos")
    events: list = []
    dec.change(lambda c, done: (events.append(("change", c.key, c.value)),
                                done()))
    dec.blob(lambda b, done: b.collect(
        lambda data: (events.append(("blob", data)), done())))

    done_evt = threading.Event()

    def produce():
        step = 192
        for off in range(0, len(wire), step):
            j.append(wire[off:off + step])
            time.sleep(0.001)
        done_evt.set()

    def source(ckpt, failures):
        if failures == 0:
            if scenario == "stall":
                plan = FaultPlan(seed=seed, stall_at=max(0, at - 32),
                                 stall_s=0.06)
            else:
                plan = FaultPlan(seed=seed, truncate_at=at)
        else:
            plan = FaultPlan(seed=seed)  # clean resume connection
        return FaultyReader(_Follower(j, ckpt.wire_offset, done_evt).read, plan)

    view = FleetView([default_snapshot])
    samples: list = []
    producer = threading.Thread(target=produce, daemon=True)
    result: dict = {}

    def drive():
        result["stats"] = run_resumable(
            source, dec, BackoffPolicy(base=0.0005, cap=0.005,
                                       max_retries=8, seed=seed),
            chunk_size=512, expected_total=len(wire),
            stall_timeout=HARD_TIMEOUT / 2)

    driver = threading.Thread(target=drive, daemon=True)
    producer.start()
    # let the producer run ahead before the receiver starts: the sweep
    # must OBSERVE lag, not race the poll loop against a sub-ms drain
    time.sleep(0.004)
    driver.start()
    deadline = time.monotonic() + HARD_TIMEOUT
    while driver.is_alive():
        assert time.monotonic() < deadline, "HANG: chaos run stuck"
        samples.append(view.poll())
        time.sleep(0.002)
    driver.join()
    producer.join(timeout=5)
    samples.append(view.poll())  # the terminal sample
    WATERMARKS.untrack("chaos")
    return samples, result.get("stats"), events, j, dec


@pytest.mark.parametrize("seed", range(20))
def test_chaos_sweep_lag_matches_ground_truth_at_every_poll(
        seed, obs_enabled):
    samples, stats, events, j, dec = _chaos_seed(seed)
    assert stats is not None, "resumable fault class must converge"

    lags = []
    for s in samples:
        entry = s["links"].get("chaos")
        if entry is None or entry.get("lag_bytes") is None:
            continue
        offs = entry["offsets"]
        # ORACLE: the aggregator's number is exactly the watermark
        # identity — no smoothing, no estimation, no fabrication
        assert entry["lag_bytes"] == max(
            0, offs["append"] - offs["parsed"])
        lags.append(entry["lag_bytes"])

    # the fault held the receiver back while the producer kept
    # appending: lag must have visibly risen...
    assert lags and max(lags) > 0, "no lag ever observed under fault"
    # ...and fallen back to EXACTLY zero at convergence
    assert lags[-1] == 0
    final = samples[-1]["links"]["chaos"]
    assert final["lag_seconds"] == 0.0
    # ground truth from journal + decoder state, independently of the
    # watermark plane: everything produced was parsed
    assert j.end == len(_CHAOS_WIRE)
    assert dec._parsed == dec.bytes == j.end
    assert dec.finished
    # ...and the decoded session is byte-identical (digests match)
    assert events == _CHAOS_EXPECTED
    # injector ground truth: truncate scenarios resumed, reconnects
    # match the recorded faults exactly
    assert stats["reconnects"] == len(stats["faults"])


@pytest.mark.slow
@pytest.mark.parametrize("seed", range(100))
def test_chaos_soak_lag_oracle(seed, obs_enabled):
    samples, stats, events, j, dec = _chaos_seed(seed)
    assert stats is not None
    final = samples[-1]["links"]["chaos"]
    assert final["lag_bytes"] == 0 and final["lag_seconds"] == 0.0
    assert events == _CHAOS_EXPECTED
    assert dec._parsed == j.end == len(_CHAOS_WIRE)


def test_chaos_flip_is_one_structured_error_never_wrong_lag(obs_enabled):
    """Corruption is not resumable: a flipped header byte must surface
    as ONE structured ProtocolError (the PR 2 contract) — and the
    watermark plane must keep reporting the honest join right through
    the failure, never a fabricated zero."""
    wire = _CHAOS_WIRE
    j = WireJournal()
    j.watermark("flip")
    dec = protocol.decode()
    dec.watermark("flip")
    dec.change(lambda c, done: done())
    dec.blob(lambda b, done: b.collect(lambda _d: done()))
    done_evt = threading.Event()
    j.append(wire)
    done_evt.set()

    def source(ckpt, failures):
        plan = FaultPlan(seed=1, flip_at=0, flip_mask=0x01) \
            if failures == 0 else FaultPlan(seed=1)
        return FaultyReader(_Follower(j, ckpt.wire_offset, done_evt).read, plan)

    view = FleetView([default_snapshot])
    with pytest.raises(ProtocolError) as ei:
        _with_watchdog(lambda: run_resumable(
            source, dec, BackoffPolicy(base=0.0005, cap=0.005,
                                       max_retries=3, seed=1),
            chunk_size=512, expected_total=len(wire),
            stall_timeout=HARD_TIMEOUT / 4))
    assert ei.value.offset is not None  # structured, with coordinates
    sample = view.poll()
    entry = sample["links"]["flip"]
    offs = entry["offsets"]
    assert entry["lag_bytes"] == max(0, offs["append"] - offs["parsed"])
    WATERMARKS.untrack("flip")


# -- the scrape endpoint ------------------------------------------------------


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read()


def test_endpoint_routes_serve_the_same_snapshot(obs_enabled):
    j = WireJournal()
    j.watermark("ep-link")
    j.append(b"z" * 77)
    with ObsHttpServer(0) as srv:
        status, body = _get(srv.url + "/snapshot")
        assert status == 200
        snap = json.loads(body)
        assert snap["watermarks"]["links"]["ep-link"]["offsets"][
            "append"] == 77
        status, body = _get(srv.url + "/metrics")
        assert status == 200
        text = body.decode()
        assert 'dat_session_wire_offset{link="ep-link",role="append"} 77' \
            in text
        status, body = _get(srv.url + "/healthz")
        assert status == 200 and json.loads(body)["ok"] is True
        status, body = _get(srv.url + "/events?n=5")
        assert status == 200
        status, _body = _get(srv.url + "/metrics/")  # trailing slash ok
        assert status == 200
    WATERMARKS.untrack("ep-link")


def test_endpoint_unknown_route_404(obs_enabled):
    with ObsHttpServer(0) as srv:
        try:
            urllib.request.urlopen(srv.url + "/nope", timeout=10)
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404


def test_healthz_degrades_to_503_when_admission_closed(obs_enabled):
    closed = {"open": False, "sessions": 9, "max_sessions": 9}
    with ObsHttpServer(0, admission_fn=lambda: closed) as srv:
        try:
            urllib.request.urlopen(srv.url + "/healthz", timeout=10)
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            rec = json.loads(e.read())
            assert rec["ok"] is False
            assert rec["stages"]["admission"]["ok"] is False


def test_healthz_stages_mirror_hub_state(obs_enabled):
    from dat_replication_protocol_tpu.hub import ReplicationHub

    hub = ReplicationHub(hash_batch=lambda items: [b"\0" * 32 for _ in items],
                         max_sessions=4)
    try:
        hz = default_healthz(hub.admission_state)
        assert hz["ok"] is True
        assert hz["stages"]["admission"]["sessions"] == 0
    finally:
        hub.close()


def test_scraping_is_read_only_and_costs_nothing_measurable(obs_enabled):
    """The overhead-budget proof: (a) 50 scrapes leave every counter
    value byte-identical — the endpoint reads locked snapshots, it
    never mutates; (b) decoding under two continuous scrapers stays
    within a COARSE wall-clock budget of the unscraped decode (the
    existing disabled-path budget test discipline: generous bound,
    CI-noise tolerant, catches a scraper that takes session locks or
    serializes the hot path)."""
    wire = _build_wire(200)

    def decode_once():
        dec = protocol.decode()
        dec.change(lambda c, done: done())
        dec.blob(lambda b, done: b.collect(lambda _d: done()))
        t0 = time.perf_counter()
        for off in range(0, len(wire), 1024):
            dec.write(wire[off:off + 1024])
        dec.end()
        assert dec.finished
        return time.perf_counter() - t0

    decode_once()  # warmup
    base = min(decode_once() for _ in range(3))

    with ObsHttpServer(0) as srv:
        before = json.loads(_get(srv.url + "/snapshot")[1])["metrics"]
        for _ in range(50):
            _get(srv.url + "/metrics")
            _get(srv.url + "/snapshot")
        after = json.loads(_get(srv.url + "/snapshot")[1])["metrics"]
        assert after["counters"] == before["counters"]  # read-only

        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    _get(srv.url + "/snapshot")
                except OSError:
                    pass

        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(2)]
        for t in threads:
            t.start()
        try:
            scraped = min(decode_once() for _ in range(3))
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
    # coarse: scraping must not serialize the decode path.  4x absorbs
    # CI noise while still catching a lock-coupled endpoint.
    assert scraped < base * 4 + 0.05, (
        f"decode {base * 1e3:.2f}ms alone vs {scraped * 1e3:.2f}ms "
        f"under continuous scraping")


def test_endpoint_dark_gate_serves_but_hot_path_stays_dark():
    """Gate off: the endpoint still answers (zeros are an honest
    answer) but the session hot path emits nothing — scraping must not
    silently enable telemetry."""
    from dat_replication_protocol_tpu.obs import events, metrics

    assert not metrics.OBS.on  # the suite default outside obs_enabled
    metrics.REGISTRY.reset()
    events.EVENTS.clear()
    wire = _build_wire(10)
    with ObsHttpServer(0) as srv:
        dec = protocol.decode()
        dec.change(lambda c, done: done())
        dec.blob(lambda b, done: b.collect(lambda _d: done()))
        for _ in range(3):
            _get(srv.url + "/metrics")
        dec.write(wire)
        dec.end()
        status, body = _get(srv.url + "/snapshot")
        snap = json.loads(body)
    assert not metrics.OBS.on, "scraping flipped the gate on"
    assert snap["metrics"]["counters"].get("decoder.bytes", 0) == 0
    assert events.EVENTS.events() == []
    metrics.REGISTRY.reset()


# -- stats-fd / endpoint / driver oracle + emit_seq ---------------------------


def test_emitter_endpoint_and_driver_agree_on_watermarks(
        obs_enabled, tmp_path):
    from dat_replication_protocol_tpu.sidecar import (
        StatsEmitter,
        snapshot_stats,
    )

    wire = _build_wire(12)
    j = WireJournal()
    j.watermark("oracle")
    j.append(wire)
    dec = protocol.decode()
    dec.watermark("oracle")
    dec.change(lambda c, done: done())
    dec.blob(lambda b, done: b.collect(lambda _d: done()))
    dec.write(wire)
    dec.end()
    assert dec.finished

    out = tmp_path / "stats.jsonl"
    fd = os.open(str(out), os.O_WRONLY | os.O_CREAT)
    try:
        emitter = StatsEmitter(fd, interval=3600)
        assert emitter.dump_once()
        assert emitter.dump_once()
    finally:
        os.close(fd)
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [ln["emit_seq"] for ln in lines] == [0, 1]  # monotonic

    with ObsHttpServer(0, snapshot_fn=snapshot_stats) as srv:
        endpoint = json.loads(_get(srv.url + "/snapshot")[1])
    file_wm = lines[-1]["watermarks"]["links"]["oracle"]["offsets"]
    http_wm = endpoint["watermarks"]["links"]["oracle"]["offsets"]
    # all three surfaces agree with the driver's own cursors
    truth = {"append": j.end, "acked": j.start, "accepted": dec.bytes,
             "parsed": dec._parsed, "checkpoint": dec._ckpt_offset}
    assert file_wm == truth
    assert http_wm == truth
    assert lines[-1]["watermarks"]["links"]["oracle"]["lag_bytes"] == 0
    WATERMARKS.untrack("oracle")


def test_file_target_detects_dropped_lines_via_emit_seq(
        obs_enabled, tmp_path):
    path = tmp_path / "t.jsonl"

    def line(seq, append):
        return json.dumps({"emit_seq": seq, "metrics": {}, "watermarks": {
            "monotonic": 1.0, "links": {"L": {
                "offsets": {"append": append, "parsed": append},
                "marks": []}}}}) + "\n"

    path.write_text(line(0, 10))
    target = FleetTarget(str(path))
    assert target.poll() is not None
    assert target.dropped_lines == 0
    # the emitter consumed seqs 1 and 2 for lines this file never got
    path.write_text(line(0, 10) + line(3, 30))
    assert target.poll() is not None
    assert target.dropped_lines == 2
    # a torn final line is skipped, not fatal
    with open(path, "a") as f:
        f.write('{"emit_seq": 4, "watermarks": {"links"')
    assert target.poll() is not None  # still the seq-3 line


def test_unreachable_target_is_visible_not_fatal(tmp_path):
    view = FleetView([str(tmp_path / "missing.jsonl")])
    sample = view.poll()
    assert sample["links"] == {}
    assert "missing.jsonl" in sample["errors"]
    rows = evaluate_slo({"max_shed": 0}, sample)
    assert any(r["check"] == "reachable" and r["status"] == "fail"
               for r in rows)


# -- event-loop lag SLO (ISSUE 18) --------------------------------------------


def _tracked_loop(name: str, lag_turn_s: float):
    """One profiler on the board with a single finished turn of
    ``lag_turn_s`` non-poll work (tick 0.05)."""
    from dat_replication_protocol_tpu.obs.loopprof import LoopProfiler

    prof = LoopProfiler(name, tick=0.05)
    prof.attach()
    prof.turn_begin(10.0)
    prof.poll_done(10.001, 1)
    prof.turn_done(10.001 + lag_turn_s, sessions=1)
    return prof


def test_loop_lag_slo_passes_on_caught_up_loop(obs_enabled):
    prof = _tracked_loop("edge-ok", 0.001)  # clean: lag exactly 0
    try:
        view = FleetView([FleetTarget(default_snapshot, name="t0")])
        sample = view.poll()
        assert sample["loops"]["t0:edge-ok"]["lag_s"] == 0.0
        rows = [r for r in evaluate_slo({"max_loop_lag_s": 0.25}, sample)
                if r["check"] == "max_loop_lag_s"]
        assert rows and all(r["status"] == "ok" for r in rows)
    finally:
        prof.detach()


def test_loop_lag_slo_fails_on_loop_behind(obs_enabled):
    prof = _tracked_loop("edge-slow", 0.6)  # 0.55s of lag
    try:
        view = FleetView([FleetTarget(default_snapshot, name="t0")])
        rows = [r for r in
                evaluate_slo({"max_loop_lag_s": 0.25}, view.poll())
                if r["check"] == "max_loop_lag_s"]
        assert rows and rows[0]["status"] == "fail"
        assert rows[0]["subject"] == "t0:edge-slow"
        assert "0.550" in rows[0]["detail"]
    finally:
        prof.detach()


def test_loop_lag_slo_fails_loudly_on_dark_loop(obs_enabled):
    """A loop whose gate is off must FAIL the check, not pass on stale
    zeros — dark telemetry is an answer of 'unknown', and the SLO gate
    treats unknown as breach."""
    from dat_replication_protocol_tpu.obs import metrics

    prof = _tracked_loop("edge-dark", 0.001)
    try:
        view = FleetView([FleetTarget(default_snapshot, name="t0")])
        metrics.OBS.on = False
        sample = view.poll()
        metrics.enable()
        assert sample["loops"]["t0:edge-dark"]["state"] == "dark"
        rows = [r for r in
                evaluate_slo({"max_loop_lag_s": 0.25}, sample)
                if r["check"] == "max_loop_lag_s"]
        assert rows and rows[0]["status"] == "fail"
        assert "dark" in rows[0]["detail"]
    finally:
        metrics.enable()
        prof.detach()


def test_loop_lag_slo_fails_when_no_target_reports_loops(obs_enabled):
    view = FleetView([FleetTarget(default_snapshot, name="t0")])
    rows = [r for r in
            evaluate_slo({"max_loop_lag_s": 0.25}, view.poll())
            if r["check"] == "max_loop_lag_s"]
    assert rows and rows[0]["status"] == "fail"
    assert "no targets report" in rows[0]["detail"]


def test_dashboard_renders_loop_lag_section(obs_enabled):
    prof = _tracked_loop("edge-dash", 0.3)
    try:
        view = FleetView([FleetTarget(default_snapshot, name="t0")])
        screen = render_dashboard(view, view.poll())
        assert "t0:edge-dash" in screen
    finally:
        prof.detach()


# -- SLO gate (the tier-1 live gate) ------------------------------------------


def _converged_two_replica_scenario():
    """The 2-replica in-process scenario the tier-1 gate runs: sender
    journal + receiver decoder, both watermarked on one link, run to
    byte-identical completion."""
    wire = _build_wire(16)
    j = WireJournal()
    j.watermark("gate")
    j.append(wire)
    dec = protocol.decode()
    dec.watermark("gate")
    dec.change(lambda c, done: done())
    dec.blob(lambda b, done: b.collect(lambda _d: done()))
    dec.write(wire)
    dec.end()
    assert dec.finished
    return j, dec


def test_fleet_check_gate_passes_on_converged_fleet(obs_enabled, tmp_path):
    _converged_two_replica_scenario()
    slo = tmp_path / "slo.json"
    slo.write_text(json.dumps({
        "max_lag_bytes": 0, "max_lag_seconds": 0.5,
        "require_converged": True, "max_shed": 0, "max_rejected": 0,
        "recompile_budget": 4, "max_events_dropped": 0,
    }))
    import io

    out = io.StringIO()
    rc = run_fleet_check([default_snapshot], str(slo), polls=2,
                         interval=0.01, out=out)
    assert rc == 0, out.getvalue()
    assert "within SLO" in out.getvalue()
    WATERMARKS.untrack("gate")


def test_fleet_check_gate_fails_on_doctored_lag(obs_enabled, tmp_path):
    wire = _build_wire(16)
    j = WireJournal()
    j.watermark("gate-bad")
    j.append(wire)
    dec = protocol.decode()
    dec.watermark("gate-bad")
    dec.change(lambda c, done: done())
    dec.blob(lambda b, done: b.collect(lambda _d: done()))
    dec.write(wire[: len(wire) // 2])  # stuck mid-wire: real lag
    slo = tmp_path / "slo.json"
    slo.write_text(json.dumps({"require_converged": True}))
    import io

    out = io.StringIO()
    rc = run_fleet_check([default_snapshot], str(slo), polls=1, out=out)
    assert rc == 1
    assert "SLO BREACH" in out.getvalue()
    WATERMARKS.untrack("gate-bad")


@pytest.mark.parametrize("content", [
    "not json at all",
    '["a", "list"]',
    "{}",
    '{"bogus_key": 1}',
    '{"max_lag_bytes": "lots"}',
    '{"require_converged": 1}',
])
def test_fleet_check_malformed_slo_fails_loudly(tmp_path, content):
    slo = tmp_path / "slo.json"
    slo.write_text(content)
    import io

    out = io.StringIO()
    rc = run_fleet_check([lambda: {"watermarks": {"links": {}}}],
                         str(slo), polls=1, out=out)
    assert rc == 1
    assert "FAIL slo" in out.getvalue()
    with pytest.raises((ValueError, json.JSONDecodeError)):
        load_slo(str(slo))


def test_fleet_check_cli_end_to_end(obs_enabled, tmp_path, capsys):
    from dat_replication_protocol_tpu.obs.__main__ import main
    from dat_replication_protocol_tpu.sidecar import snapshot_stats

    _converged_two_replica_scenario()
    target = tmp_path / "replica.jsonl"
    snap = snapshot_stats()
    snap["emit_seq"] = 0
    target.write_text(json.dumps(snap) + "\n")
    slo = tmp_path / "slo.json"
    slo.write_text(json.dumps({"max_lag_bytes": 0}))
    assert main(["fleet", str(target), "--check", str(slo),
                 "--polls", "1"]) == 0
    assert "within SLO" in capsys.readouterr().out
    # snapshot_stats embeds the staged healthz record, so file targets
    # can evaluate require_healthz...
    slo.write_text(json.dumps({"max_lag_seconds": 0.0,
                               "require_healthz": True}))
    assert main(["fleet", str(target), "--check", str(slo),
                 "--polls", "1"]) == 0
    # ...and a snapshot WITHOUT one (a bare/doctored record) must make
    # the gate FAIL, never silently skip the stage
    del snap["healthz"]
    target.write_text(json.dumps(snap) + "\n")
    assert main(["fleet", str(target), "--check", str(slo),
                 "--polls", "1"]) == 1
    WATERMARKS.untrack("gate")


def test_dashboard_renders_one_screen(obs_enabled):
    _converged_two_replica_scenario()
    view = FleetView([FleetTarget(default_snapshot, name="replica-a")])
    sample = view.poll(healthz=True)
    frame = render_dashboard(view, sample)
    assert "replica-a" in frame
    assert "gate" in frame  # the link row
    assert "lag_bytes" in frame
    assert "\x1b[" not in frame  # plain text; the CLI owns the clear
    WATERMARKS.untrack("gate")


# -- N-log timeline (the offline mirror) -------------------------------------


def _frame_line(span: str, seq: int, offset: int, wire_len: int,
                link=None) -> str:
    fields = {"offset": offset, "wire_len": wire_len}
    if link is not None:
        fields["link"] = link
    return json.dumps({"span": span, "seq": seq, "ts": float(seq),
                       "fields": fields}) + "\n"


def _write_log(path, span, frames, link=None):
    path.write_text("".join(
        _frame_line(span, i, off, wl, link)
        for i, (off, wl) in enumerate(frames)))


def test_timeline_three_logs_clean_fanout_merge(tmp_path, capsys):
    from dat_replication_protocol_tpu.obs.__main__ import main

    frames = [(0, 10), (10, 20), (30, 5)]
    s = tmp_path / "sender.jsonl"
    r1 = tmp_path / "r1.jsonl"
    r2 = tmp_path / "r2.jsonl"
    _write_log(s, "encoder.frame", frames)
    _write_log(r1, "decoder.frame", frames)
    _write_log(r2, "decoder.frame", frames)
    rc = main(["timeline", str(s), str(r1), str(r2), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["flags"] == []
    # fan-out shape: ONE emitter serves BOTH dispatch streams
    assert len(out["links"]) == 2
    assert all(ln["emitter"] == "sender.jsonl" for ln in out["links"])
    assert {ln["dispatcher"] for ln in out["links"]} == \
        {"r1.jsonl", "r2.jsonl"}
    assert set(out["peers"]) == {"sender.jsonl", "r1.jsonl", "r2.jsonl"}
    # merged rows keyed on offset, emitter-first at equal offsets
    first = [w for w in out["timeline"] if w["offset"] == 0]
    assert first[0]["role"] == "sender.jsonl"


def test_timeline_three_logs_doctored_gap_flagged(tmp_path, capsys):
    from dat_replication_protocol_tpu.obs.__main__ import main

    frames = [(0, 10), (10, 20), (30, 5)]
    s = tmp_path / "sender.jsonl"
    r1 = tmp_path / "r1.jsonl"
    r2 = tmp_path / "r2.jsonl"
    _write_log(s, "encoder.frame", frames)
    _write_log(r1, "decoder.frame", frames)
    _write_log(r2, "decoder.frame", [(0, 10), (30, 5)])  # dropped a frame
    rc = main(["timeline", str(s), str(r1), str(r2), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    flagged = {f["flag"] for f in out["flags"]}
    assert "gap" in flagged  # r2's own coverage hole
    assert "peer-divergence" in flagged  # vs its paired emitter


def test_timeline_link_labels_beat_coverage_matching(tmp_path, capsys):
    from dat_replication_protocol_tpu.obs.__main__ import main

    # two independent wires with IDENTICAL coverage: only the link
    # label can pair them correctly
    frames = [(0, 10), (10, 10)]
    sa = tmp_path / "sa.jsonl"
    sb = tmp_path / "sb.jsonl"
    ra = tmp_path / "ra.jsonl"
    rb = tmp_path / "rb.jsonl"
    _write_log(sa, "encoder.frame", frames, link="wire-a")
    _write_log(sb, "encoder.frame", frames, link="wire-b")
    _write_log(ra, "decoder.frame", frames, link="wire-a")
    _write_log(rb, "decoder.frame", frames, link="wire-b")
    rc = main(["timeline", str(sa), str(sb), str(ra), str(rb), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    pair = {ln["link"]: (ln["emitter"], ln["dispatcher"])
            for ln in out["links"]}
    assert pair == {"wire-a": ("sa.jsonl", "ra.jsonl"),
                    "wire-b": ("sb.jsonl", "rb.jsonl")}


def test_timeline_two_logs_unchanged(tmp_path, capsys):
    # the exactly-2 path keeps the classic sender/receiver JSON shape
    from dat_replication_protocol_tpu.obs.__main__ import main

    frames = [(0, 10), (10, 20)]
    s = tmp_path / "s.jsonl"
    r = tmp_path / "r.jsonl"
    _write_log(s, "encoder.frame", frames)
    _write_log(r, "decoder.frame", frames)
    rc = main(["timeline", str(s), str(r), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["sender"]["frames"] == 2 and out["receiver"]["frames"] == 2


# -- sidecar integration ------------------------------------------------------


def test_sidecar_obs_http_flag_serves_session_watermarks(obs_enabled):
    """--obs-http end to end: a real sidecar TCP session's receive
    cursors appear on /snapshot while the session runs, and the link
    vanishes once the session ends (bounded cardinality)."""
    import socket

    from dat_replication_protocol_tpu.obs.http import ObsHttpServer
    from dat_replication_protocol_tpu.sidecar import (
        serve_tcp,
        snapshot_stats,
    )

    wire = _build_wire(6)
    srv = ObsHttpServer(0, snapshot_fn=snapshot_stats).start()
    ready = threading.Event()
    port_box: dict = {}

    def _serve():
        serve_tcp("127.0.0.1", 0, max_sessions=1,
                  ready_cb=lambda p: (port_box.update(port=p),
                                      ready.set()),
                  drain_timeout=10)

    t = threading.Thread(target=_serve, daemon=True)
    t.start()
    assert ready.wait(10)
    with socket.create_connection(("127.0.0.1", port_box["port"]),
                                  timeout=10) as conn:
        conn.sendall(wire)
        conn.shutdown(socket.SHUT_WR)
        while conn.recv(4096):
            pass
    t.join(timeout=10)
    # the session closed: its link must be GONE from the board.  The
    # client's EOF races the session thread's own epilogue (serve_tcp
    # returns once the connection is handed off), so poll briefly.
    deadline = time.monotonic() + 10
    while True:
        snap = json.loads(_get(srv.url + "/snapshot")[1])
        gone = not any(k.startswith("c1:")
                       for k in snap["watermarks"]["links"])
        if gone or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    srv.close()
    assert gone


# -- mesh convergence SLO plumbing (ISSUE 19) --------------------------------


from dat_replication_protocol_tpu.obs.fleet import (  # noqa: E402
    MESH_SLO_KEYS,
    _join_mesh,
    mesh_rounds_floor,
)


def _prop_snap(links=None, frontier=None, p99=None, count=0):
    return {"monotonic": 0.0, "links": links or {},
            "frontier": frontier or {},
            "exchange_seconds": {"count": count, "p50": p99, "p99": p99}}


def _link(rnd, *, outcome="progress", div_rec=2, div_b=128, ok_age=0.5):
    return {"role": "initiator", "round": rnd, "outcome": outcome,
            "divergence_records": div_rec, "divergence_bytes": div_b,
            "wire_bytes": 256, "seconds": 0.01, "exchanges": 1,
            "failures": 0, "error": None, "age_s": 0.1,
            "last_success_age_s": ok_age}


def test_join_mesh_freshest_link_wins_and_p99_is_the_max():
    snaps = {
        "t0": {"propagation": _prop_snap(
            links={"r0->r1": _link(2, div_rec=5)},
            frontier={"r0": {"digest": "aa", "records": 3, "round": 2}},
            p99=0.02, count=4)},
        "t1": {"propagation": _prop_snap(
            links={"r0->r1": _link(4, div_rec=1)},
            frontier={"r1": {"digest": "bb", "records": 2, "round": 4}},
            p99=0.08, count=6)},
    }
    mesh = _join_mesh(snaps)
    assert mesh["links"]["r0->r1"]["round"] == 4
    assert mesh["links"]["r0->r1"]["divergence_records"] == 1
    assert mesh["links"]["r0->r1"]["target"] == "t1"
    assert mesh["exchange_p99_s"] == 0.08
    assert mesh["exchange_count"] == 10
    # frontiers differ: the pair is NOT converged, watermark stands
    pair = mesh["pairs"]["r0<->r1"]
    assert not pair["converged"]
    assert pair["divergence_records"] == 1


def test_join_mesh_frontier_equality_overrides_stale_watermark():
    """A link watermark is the diff at the pair's LAST exchange; once
    both frontiers are byte-identical the pair's divergence is exactly
    0 whatever a stale watermark says (the smoke-test lesson: a link
    that last exchanged at round 1 with diff 4 and never re-exchanged
    must not read as diverged after the mesh converged)."""
    snaps = {"t0": {"propagation": _prop_snap(
        links={"r0->r1": _link(1, div_rec=4, div_b=400)},
        frontier={"r0": {"digest": "cc", "records": 5, "round": 3},
                  "r1": {"digest": "cc", "records": 5, "round": 3}})}}
    pair = _join_mesh(snaps)["pairs"]["r0<->r1"]
    assert pair["converged"]
    assert pair["divergence_records"] == 0
    assert pair["divergence_bytes"] == 0


def test_join_mesh_empty_when_nothing_reports():
    assert _join_mesh({"t0": {"gossip": {}}, "t1": None}) == {}


@pytest.mark.parametrize("key", sorted(MESH_SLO_KEYS))
def test_mesh_slo_keys_must_be_numeric(tmp_path, key):
    path = tmp_path / "slo.json"
    path.write_text(json.dumps({"gossip": {key: "fast"}}))
    with pytest.raises(ValueError, match="must be a number"):
        load_slo(str(path))
    path.write_text(json.dumps({"gossip": {key: 10}}))
    assert load_slo(str(path))["gossip"][key] == 10


def test_mesh_slo_dark_plane_fails_loudly():
    slo = {"gossip": {"max_divergence_bytes": 0}}
    sample = {"links": {}, "gossip": {"t0": {
        "replica": "r0", "round": 3, "rounds_behind": 0, "records": 1,
        "digest": "aa", "quarantined": [], "quarantine": {},
        "suspicion": {}}}, "mesh": {}}
    rows = [r for r in evaluate_slo(slo, sample)
            if r["check"] == "gossip.mesh"]
    assert rows and rows[0]["status"] == "fail"
    assert "no targets report propagation records" in rows[0]["detail"]


def test_mesh_slo_unreachable_convergence_bound_is_a_misconfig():
    """A max_convergence_rounds below the epidemic floor fails as an
    SLO bug, not as a mesh failure — an unreachable gate is a
    misconfiguration, never a standard."""
    assert mesh_rounds_floor(2) == 13
    assert mesh_rounds_floor(4) == 16
    assert mesh_rounds_floor(64) == 28
    mesh = {"frontier": {f"r{i}": {"digest": "aa", "round": 2}
                         for i in range(4)},
            "links": {}, "pairs": {}, "exchange_p99_s": None,
            "exchange_count": 0}
    slo = {"gossip": {"max_convergence_rounds": 15}}
    rows = evaluate_slo(slo, {"links": {}, "gossip": {}, "mesh": mesh})
    (row,) = [r for r in rows
              if r["check"] == "gossip.max_convergence_rounds"]
    assert row["status"] == "fail"
    assert "unreachable SLO" in row["detail"]
    # at the floor it evaluates for real — converged at round 2 passes
    slo = {"gossip": {"max_convergence_rounds": 16}}
    rows = evaluate_slo(slo, {"links": {}, "gossip": {}, "mesh": mesh})
    (row,) = [r for r in rows
              if r["check"] == "gossip.max_convergence_rounds"]
    assert row["status"] == "ok"
    assert "converged at round 2" in row["detail"]


def test_mesh_slo_silently_dead_link_fails_age_check():
    mesh = {"frontier": {"r0": {"digest": "aa", "round": 1},
                         "r1": {"digest": "bb", "round": 1}},
            "links": {"r0->r1": dict(_link(1), last_success_age_s=None)},
            "pairs": {"r0<->r1": {"round": 1, "converged": False,
                                  "divergence_records": 2,
                                  "divergence_bytes": 128,
                                  "last_success_age_s": None,
                                  "outcome": "transport"}},
            "exchange_p99_s": 0.01, "exchange_count": 1}
    slo = {"gossip": {"max_exchange_age_s": 60}}
    rows = evaluate_slo(slo, {"links": {}, "gossip": {}, "mesh": mesh})
    (row,) = [r for r in rows
              if r["check"] == "gossip.max_exchange_age_s"]
    assert row["status"] == "fail"
    assert "silently-dead link" in row["detail"]


def test_dashboard_renders_the_mesh_matrix():
    sample = {
        "ts": 0.0, "targets": {}, "links": {}, "dropped_lines": {},
        "gossip": {"t0": {"replica": "r0", "round": 3,
                          "rounds_behind": 0, "records": 4,
                          "digest": "aa" * 16, "quarantined": ["rX"],
                          "quarantine": {"rX": {"arm": "wrong-symbol",
                                                "frame": 2,
                                                "offset": 17}},
                          "suspicion": {}}},
        "mesh": {"links": {}, "frontier": {},
                 "pairs": {"r0<->r1": {"round": 3, "converged": True,
                                       "divergence_records": 0,
                                       "divergence_bytes": 0,
                                       "last_success_age_s": 0.25,
                                       "outcome": "converged"}},
                 "exchange_p99_s": 0.0123, "exchange_count": 42},
    }
    view = FleetView([FleetTarget(lambda: {}, name="t0")])
    frame = render_dashboard(view, sample)
    assert "r0<->r1" in frame
    assert "converged" in frame
    assert "exchange p99 0.0123s over 42 exchange(s)" in frame
    assert "quarantine r0: rX arm=wrong-symbol frame=2 offset=17" \
        in frame
