"""The event-loop flight deck (ISSUE 18): per-turn phase accounting,
loop-lag watermarks, and the sampling turn profiler.

Three contracts under test:

* **Span tiling** — recorded ``edge.turn`` spans tile the loop's wall
  time exactly (``span[i+1].ts == span[i].ts + span[i].dur``, float
  equality): idle turns coalesce into the next active span, and the
  shutdown flush closes the trailing idle stretch.
* **The dark path** — with the obs gate off the dispatcher runs the
  certified dark twin: ONE attribute load, no profiler names in its
  bytecode, zero ``edge.turn`` spans, zero ``edge.loop.turns``.
* **Lag semantics** — ``lag = max(0, work_s - tick)``: a clean turn is
  *exactly* 0.0 (the selector's wait is sanctioned, not lag), a stalled
  turn reads its overrun, the live view extrapolates mid-turn, and the
  watermark board exports ``edge.loop.lag{loop=}`` only while live.
* **The read phase's second clock** (ISSUE 37) — the loop thread's CPU
  seconds inside the reads that feed ``edge.turn.read_s``, as
  ``edge.turn.read_cpu_s``: on the turns that take it (one in
  ``CPU_CLOCK_EVERY``, the first included) one observation beside its
  wall twin's, never more seconds than it, by the lit twin only.
"""

import dis
import socket
import threading
import time

import pytest

from dat_replication_protocol_tpu.edge import EdgeLoop
from dat_replication_protocol_tpu.hub import ReplicationHub
from dat_replication_protocol_tpu.obs import loopprof as loopprof_mod
from dat_replication_protocol_tpu.obs.loopprof import LoopProfiler, PHASES
from dat_replication_protocol_tpu.obs.tracing import SPANS

from test_wire_fixtures import SESSION_1


def _recv_all(sock: socket.socket) -> bytes:
    parts = []
    while True:
        d = sock.recv(65536)
        if not d:
            return b"".join(parts)
        parts.append(d)


def _run_sessions(loop: EdgeLoop, n: int) -> None:
    """Serve ``n`` reference sessions through a bound loop thread and
    join it (max_sessions must equal ``n``)."""
    port = loop.bind("127.0.0.1", 0)
    t = threading.Thread(target=loop.serve, daemon=True)
    t.start()
    try:
        for _ in range(n):
            c = socket.create_connection(("127.0.0.1", port), timeout=10)
            c.sendall(SESSION_1)
            c.shutdown(socket.SHUT_WR)
            assert _recv_all(c)
            c.close()
    finally:
        loop.close()
        t.join(timeout=10)
    assert not t.is_alive()


# -- span tiling -------------------------------------------------------------

def test_edge_turn_spans_tile_exactly(obs_enabled):
    """Consecutive recorded spans for one loop leave no gap and no
    overlap: each span's ts is the previous span's ts + dur, exactly —
    the anchor the profiler carries IS the previous span's end."""
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, max_sessions=3, tick=0.01, profile_every=1)
    try:
        _run_sessions(loop, 3)
    finally:
        hub.close()
    spans = [r for r in SPANS.spans("edge.turn")
             if r["fields"]["loop"] == loop.profiler.name]
    assert len(spans) >= 3  # at least one active span per session
    for prev, nxt in zip(spans, spans[1:]):
        assert nxt["ts"] == prev["ts"] + prev["dur"]  # float-exact
    # every span carries the full phase vocabulary as _s fields
    for r in spans:
        f = r["fields"]
        if f["work_s"] == 0.0:
            continue  # trailing idle flush carries the short shape
        for name in PHASES:
            assert name.replace("-", "_") + "_s" in f
        assert f["lag_s"] >= 0.0 and f["tick"] == 0.01


def test_idle_turns_coalesce_and_flush_covers_the_tail(obs_enabled):
    """An idle stretch after the last session still reaches the span
    log: detach() flushes a trailing idle span whose poll time covers
    the quiet turns, keeping the tiling complete to shutdown."""
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, tick=0.005, profile_every=1)
    port = loop.bind("127.0.0.1", 0)
    t = threading.Thread(target=loop.serve, daemon=True)
    t.start()
    try:
        c = socket.create_connection(("127.0.0.1", port), timeout=10)
        c.sendall(SESSION_1)
        c.shutdown(socket.SHUT_WR)
        assert _recv_all(c)
        c.close()
        time.sleep(0.1)  # the loop idles: >= a dozen quiet turns
    finally:
        loop.close()
        t.join(timeout=10)
    spans = [r for r in SPANS.spans("edge.turn")
             if r["fields"]["loop"] == loop.profiler.name]
    assert spans, "no spans recorded"
    tail = spans[-1]
    # the flush span: multiple coalesced turns, zero work, poll covers
    assert tail["fields"]["turns"] >= 2
    assert tail["fields"]["work_s"] == 0.0
    assert tail["fields"]["poll_wait_s"] > 0.0
    for prev, nxt in zip(spans, spans[1:]):
        assert nxt["ts"] == prev["ts"] + prev["dur"]


# -- the dark path -----------------------------------------------------------

def test_dark_turn_never_touches_the_profiler():
    """Bytecode contract: the dark twin's code object references no
    profiler name at all; the per-turn gate fork lives in
    _dispatch_loop."""
    dark = EdgeLoop._dark_turn.__code__
    assert "profiler" not in dark.co_names
    assert not any("prof" in n for n in dark.co_names + dark.co_varnames)
    dispatch = EdgeLoop._dispatch_loop.__code__
    assert "_OBS" in dispatch.co_names and "on" in dispatch.co_names
    assert "_lit_turn" in dispatch.co_names
    assert "_dark_turn" in dispatch.co_names


def test_gate_off_records_nothing():
    """Behavioral dark-path check: gate off, a full session runs, and
    neither the span log nor the turn counter nor the profiler's own
    turn count moves."""
    from dat_replication_protocol_tpu.obs import metrics
    from dat_replication_protocol_tpu.obs.watermarks import WATERMARKS

    was_on = metrics.OBS.on
    metrics.OBS.on = False
    try:
        before = len(SPANS.spans("edge.turn"))
        hub = ReplicationHub(linger_s=0.002)
        loop = EdgeLoop(hub, max_sessions=1, tick=0.01)
        try:
            _run_sessions(loop, 1)
        finally:
            hub.close()
        assert len(SPANS.spans("edge.turn")) == before
        assert loop.profiler.turns == 0
        assert loop.profiler.lag_max_s == 0.0
    finally:
        metrics.OBS.on = was_on
        WATERMARKS.untrack_loop(loop.profiler.name)


# -- the read phase's second clock (ISSUE 37) --------------------------------

def _read_pair(metrics) -> tuple:
    h = metrics.snapshot()["histograms"]
    return h["edge.turn.read_s"], h["edge.turn.read_cpu_s"]


@pytest.mark.parametrize("turns, want_counts, want_wall, want_cpu", [
    # one turn, two sessions read: ONE observation of each clock
    ([[("read", 0.004, 0.003), ("read", 0.002, 0.0005)]],
     (1, 1), 0.006, 0.0035),
    # a turn that only transmitted feeds neither
    ([[("tx", 0.004, None)]], (0, 0), 0.0, 0.0),
    # two turns, the site took the CPU clock on the first alone: the
    # wall twin has both, the CPU twin one, and nothing leaks over
    ([[("read", 0.004, 0.004), ("tx", 0.001, None)],
      [("read", 0.010, None)]], (2, 1), 0.014, 0.004),
    # a read that spent no CPU at all (all of it waiting) still pairs
    ([[("read", 0.005, 0.0)]], (1, 1), 0.005, 0.0),
])
def test_read_cpu_twin_is_observed_with_its_wall_twin(
        obs_enabled, turns, want_counts, want_wall, want_cpu):
    prof = LoopProfiler("unit-cpu", tick=0.05)
    t = 100.0
    for phases in turns:
        prof.turn_begin(t)
        prof.poll_done(t + 0.001, 1)
        for name, seconds, cpu in phases:
            prof.account(name, "s1", seconds, 10)
            if cpu is not None:
                prof.read_cpu(cpu)
        t += 0.02
        prof.turn_done(t, sessions=1)
    wall, cpu = _read_pair(obs_enabled)
    assert (wall["count"], cpu["count"]) == want_counts
    assert wall["sum"] == pytest.approx(want_wall)
    assert cpu["sum"] == pytest.approx(want_cpu)
    assert cpu["sum"] <= wall["sum"]


def test_the_profiler_says_which_turns_take_the_second_clock():
    from dat_replication_protocol_tpu.obs.metrics import CPU_CLOCK_EVERY

    prof = LoopProfiler("unit-beat", tick=0.05)
    taken = []
    for i in range(10 * CPU_CLOCK_EVERY):
        prof.turn_begin(float(i))
        taken.append(prof.cpu_turn)
        prof.poll_done(i + 0.001, 0)
        prof.turn_done(i + 0.002)
    assert taken[0] is True and 7 <= sum(taken) <= 13


def test_a_served_loop_feeds_both_clocks_of_its_read_phase(obs_enabled,
                                                           monkeypatch):
    """Through the lit dispatcher itself, every turn made to take the
    second clock: each turn that read observed both clocks, and the
    thread's CPU seconds fit inside its wall seconds."""
    monkeypatch.setattr(loopprof_mod, "_cpu_clock_visit", lambda n: True)
    hub = ReplicationHub(linger_s=0.002)
    loop = EdgeLoop(hub, max_sessions=3, tick=0.01, profile_every=1)
    try:
        _run_sessions(loop, 3)
    finally:
        hub.close()
    wall, cpu = _read_pair(obs_enabled)
    assert wall["count"] == cpu["count"] >= 3
    assert 0.0 < cpu["sum"] <= wall["sum"]


def test_only_the_lit_branches_read_the_cpu_clock():
    """``_dark_turn`` names no clock but the one it had; the shared
    per-session turns read ``thread_time`` after their ``prof`` test
    only, in bytecode order."""
    assert "thread_time" not in EdgeLoop._dark_turn.__code__.co_names
    assert "thread_time" not in EdgeLoop._lit_turn.__code__.co_names
    for fn in (EdgeLoop._io_turn, EdgeLoop._fan_reads):
        seen_prof = False
        reads = 0
        for ins in dis.get_instructions(fn):
            if ins.argval == "prof":
                seen_prof = True
            if ins.argval == "thread_time":
                assert seen_prof, fn.__qualname__
                reads += 1
        assert reads == 2, fn.__qualname__


# -- lag semantics (unit level: the profiler drives itself) ------------------

def test_clean_turn_lag_is_exactly_zero():
    prof = LoopProfiler("unit", tick=0.05)
    t0 = 100.0
    prof.turn_begin(t0)
    prof.poll_done(t0 + 0.05, 0)          # full-tick quiet poll
    prof.turn_done(t0 + 0.0501)           # 100us of sweep work
    assert prof.lag_s == 0.0              # EXACTLY zero, not epsilon
    assert prof.lag_max_s == 0.0
    assert prof.turns == 1 and prof.active_turns == 0


def test_stalled_turn_reads_its_overrun():
    prof = LoopProfiler("unit", tick=0.05)
    t0 = 100.0
    prof.turn_begin(t0)
    prof.poll_done(t0 + 0.001, 1)
    prof.account("read", "c1:peer", 0.3, 4096)
    prof.turn_done(t0 + 0.001 + 0.35, sessions=1)
    assert abs(prof.lag_s - 0.30) < 1e-9  # 0.35 work - 0.05 tick
    assert prof.lag_max_s == prof.lag_s
    assert prof.active_turns == 1


def test_live_lag_extrapolates_mid_turn():
    prof = LoopProfiler("unit", tick=0.05)
    prof.turn_begin(100.0)
    prof.poll_done(100.001, 1)            # work begins, never ends
    assert prof.live_lag(now=100.001 + 0.5) > 0.4
    assert prof.oldest_ready_s(now=100.001 + 0.5) > 0.4
    # the export flags it behind (gate state only names live vs dark)
    assert prof.export()["behind"]
    prof.turn_done(100.001 + 0.5, sessions=1)
    assert prof.live_lag(now=200.0) == prof.lag_s  # no extrapolation idle


def test_turn_profiler_top_k_ranks_heaviest_sessions():
    """Every overrun turn carries a top-K capture ranked by (seconds,
    bytes), each entry naming its dominant phase."""
    prof = LoopProfiler("unit", tick=0.01, top_k=2)
    t0 = 50.0
    prof.turn_begin(t0)
    prof.poll_done(t0 + 0.001, 3)
    prof.account("read", "c1:a", 0.002, 100)
    prof.account("read", "c2:b", 0.200, 9000)
    prof.account("tx", "c2:b", 0.010, 500)
    prof.account("tx", "c3:c", 0.050, 50)
    prof.turn_done(t0 + 0.001 + 0.262, sessions=3)
    spans = [r for r in SPANS.spans("edge.turn")
             if r["fields"]["loop"] == "unit"]
    top = spans[-1]["fields"]["top"]
    assert [e["session"] for e in top] == ["c2:b", "c3:c"]  # top_k=2
    assert top[0]["phase"] == "read"      # 0.200 read vs 0.010 tx
    assert top[0]["bytes"] == 9500
    assert top[1]["phase"] == "tx"


def test_sampling_gates_top_capture_on_clean_turns():
    """Without lag, only every sample_every-th ACTIVE turn carries the
    top field — the capture is amortized, not per-turn."""
    prof = LoopProfiler("unit2", tick=10.0, sample_every=4)
    t = 0.0
    for i in range(8):
        prof.turn_begin(t)
        prof.poll_done(t + 0.001, 1)
        prof.account("read", "c1:a", 0.001, 10)
        t += 0.01
        prof.turn_done(t, sessions=1)
    spans = [r for r in SPANS.spans("edge.turn")
             if r["fields"]["loop"] == "unit2"]
    assert len(spans) == 8
    with_top = [i for i, r in enumerate(spans) if "top" in r["fields"]]
    assert with_top == [3, 7]  # active turns 4 and 8


# -- the watermark board + /healthz ------------------------------------------

def test_loop_lag_gauges_ride_the_watermark_board(obs_enabled):
    from dat_replication_protocol_tpu.obs.watermarks import WATERMARKS

    prof = LoopProfiler("wmtest", tick=0.05)
    prof.attach()
    try:
        prof.turn_begin(10.0)
        prof.poll_done(10.001, 1)
        prof.turn_done(10.001 + 0.25, sessions=1)  # 0.2s lag
        snap = obs_enabled.REGISTRY.snapshot()["gauges"]
        assert snap["edge.loop.lag{loop=wmtest}"] == prof.lag_s
        assert snap["edge.loop.lag_max{loop=wmtest}"] == prof.lag_max_s
        board = WATERMARKS.snapshot()
        assert board["loops"]["wmtest"]["state"] == "live"
        assert board["loops"]["wmtest"]["behind"]
    finally:
        prof.detach()
    assert "loops" not in WATERMARKS.snapshot() or \
        "wmtest" not in WATERMARKS.snapshot().get("loops", {})


def test_dark_loop_exports_state_not_gauges(obs_enabled):
    from dat_replication_protocol_tpu.obs import metrics
    from dat_replication_protocol_tpu.obs.watermarks import WATERMARKS

    prof = LoopProfiler("darkwm", tick=0.05)
    prof.attach()
    try:
        metrics.OBS.on = False
        snap = metrics.REGISTRY.snapshot()["gauges"]
        assert "edge.loop.lag{loop=darkwm}" not in snap
        assert WATERMARKS.snapshot()["loops"]["darkwm"]["state"] == "dark"
    finally:
        metrics.enable()
        prof.detach()


def test_healthz_loop_lag_stage_flips_and_recovers(obs_enabled):
    """/healthz grows a loop_lag stage: behind => ok False naming the
    loop, caught up => ok True — and a process with no loops at all
    has no stage (host-only legs stay unchanged)."""
    from dat_replication_protocol_tpu.obs.http import default_healthz

    hz = default_healthz()
    assert "loop_lag" not in hz["stages"]

    prof = LoopProfiler("hz", tick=0.05)
    prof.attach()
    try:
        # mid-stall: work began long ago and never finished
        prof.turn_begin(time.monotonic() - 1.0)
        prof.poll_done(time.monotonic() - 1.0, 1)
        hz = default_healthz()
        assert not hz["ok"]
        assert hz["stages"]["loop_lag"]["behind"] == ["hz"]
        assert hz["stages"]["loop_lag"]["lag_s"]["hz"] > 0.5
        # the stall ends; the next clean turn recovers the probe
        prof.turn_done(time.monotonic())
        prof.turn_begin(time.monotonic())
        prof.poll_done(time.monotonic(), 0)
        prof.turn_done(time.monotonic())
        hz = default_healthz()
        assert hz["ok"] and hz["stages"]["loop_lag"]["ok"]
    finally:
        prof.detach()
