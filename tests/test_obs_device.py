"""Device-path telemetry (ISSUE 5): recompile sentinel, backend-init
watchdog, chiplock metrics, perf-budget gate.

The sentinel's acceptance shape: a deliberately shape-UNSTABLE jit
site is counted trace-by-trace (and flagged over budget), while a
bucketed/shape-stable one stays silent after its first specialization.
The watchdog's: a stubbed slow init fires the deadline and the flight
bundle's manifest names the stage it was stuck in.  The gate's: the
checked-in snapshot passes against the checked-in budgets; a doctored
regression fails.
"""

import io
import json
import os
import time

import numpy as np
import pytest

import bench
from dat_replication_protocol_tpu.obs import device as obs_device
from dat_replication_protocol_tpu.obs import events as obs_events
from dat_replication_protocol_tpu.obs import flight as obs_flight
from dat_replication_protocol_tpu.obs import metrics as obs_metrics
from dat_replication_protocol_tpu.obs import perf as obs_perf
from dat_replication_protocol_tpu.obs.device import (
    BackendInitWatchdog,
    RecompileBudget,
    SENTINEL,
    jit_site,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS = os.path.join(REPO, "artifacts", "perf_budgets.json")
SNAPSHOT = os.path.join(REPO, "artifacts", "perf_snapshot_host.json")


# -- recompile sentinel -------------------------------------------------------


def test_sentinel_counts_shape_unstable_jit(obs_enabled):
    """The unbucketed-batch-size failure mode (ops/blake2b.py's
    bucketing comment): every distinct shape is a fresh trace, and the
    sentinel must count each one."""
    import jax

    f = jit_site("test.unstable", jax.jit(lambda x: x + 1))
    for n in range(1, 6):
        f(np.ones((n,), np.float32))
    snap = SENTINEL.snapshot()["test.unstable"]
    assert snap == {"calls": 5, "traces": 5}
    events = obs_events.EVENTS.events("device.jit.trace")
    assert len(events) == 5
    sigs = [e["fields"]["signature"] for e in events]
    assert sigs[0] == "(1,)float32" and sigs[-1] == "(5,)float32"
    assert obs_metrics.REGISTRY.counter("device.jit.traces").value == 5
    assert obs_metrics.REGISTRY.counter("device.jit.calls").value == 5


def test_sentinel_silent_for_bucketed_shapes(obs_enabled):
    """A bucketed site (one padded shape reused) traces once, then
    every later call is a cache hit — no further trace events."""
    import jax

    f = jit_site("test.bucketed", jax.jit(lambda x: x * 2))
    for _ in range(8):
        f(np.ones((16,), np.float32))
    snap = SENTINEL.snapshot()["test.bucketed"]
    assert snap == {"calls": 8, "traces": 1}
    assert len(obs_events.EVENTS.events("device.jit.trace")) == 1
    assert RecompileBudget(2).ok()


def test_sentinel_budget_flags_offender_once(obs_enabled):
    import jax

    f = jit_site("test.offender", jax.jit(lambda x: x + 1))
    for n in range(1, obs_device.DEFAULT_RECOMPILE_BUDGET + 4):
        f(np.ones((n,), np.float32))
    over = RecompileBudget(obs_device.DEFAULT_RECOMPILE_BUDGET).check()
    assert over and over[0]["site"] == "test.offender"
    assert over[0]["traces"] == obs_device.DEFAULT_RECOMPILE_BUDGET + 3
    # the breach event fires exactly once per site per process
    breaches = obs_events.EVENTS.events("device.jit.recompile_budget")
    assert len(breaches) == 1
    assert breaches[0]["fields"]["site"] == "test.offender"
    assert breaches[0]["fields"]["budget"] == \
        obs_device.DEFAULT_RECOMPILE_BUDGET


def test_sentinel_fallback_counter_without_cache_introspection(obs_enabled):
    """A callable with no ``_cache_size`` (custom engines, wrappers)
    rides the arg-signature fallback closure."""
    f = jit_site("test.fallback", lambda x, k=1: x)
    f(np.ones((2, 2)))
    f(np.ones((2, 2)))
    f(np.ones((4, 2)))
    f(np.ones((2, 2)), k=2)  # static kwarg change = new specialization
    assert SENTINEL.snapshot()["test.fallback"] == {"calls": 4, "traces": 3}


def test_sentinel_dark_while_gate_off():
    """Gate off: the wrapper is a pass-through — no stats, no events,
    no counters (the zero-telemetry contract)."""
    obs_metrics.disable()
    SENTINEL.reset_for_tests()
    calls = []
    f = jit_site("test.dark", lambda x: calls.append(x) or x)
    f(1)
    f(2)
    assert calls == [1, 2]  # the wrapped fn ran
    assert SENTINEL.snapshot() == {}


def test_sentinel_wrapper_delegates_jit_attributes(obs_enabled):
    import jax

    inner = jax.jit(lambda x: x + 1)
    f = jit_site("test.delegate", inner)
    assert f.__wrapped__ is inner
    # PjitFunction surface stays reachable through the wrapper
    assert callable(f.lower)


def test_sentinel_disabled_path_is_gate_bound():
    """Disabled-path budget (same coarse discipline as
    test_obs_metrics): the wrapper must cost about one gate check +
    one call — bound it at a generous absolute per-call budget."""
    obs_metrics.disable()
    f = jit_site("test.budget", lambda x: x)
    N = 100_000
    f(1)  # warm
    t0 = time.perf_counter()
    for _ in range(N):
        f(1)
    dt = time.perf_counter() - t0
    assert dt < N * 10e-6, f"disabled jit_site {dt / N * 1e9:.0f}ns/call"
    assert SENTINEL.snapshot().get("test.budget") is None


def test_repo_jit_entry_points_ride_the_sentinel(obs_enabled):
    """The wired sites: one real blake2b batch through the ops layer
    must show up in the sentinel snapshot and move the transfer
    counters."""
    from dat_replication_protocol_tpu.ops.blake2b import blake2b_batch

    digs = blake2b_batch([b"a" * 100, b"b" * 200])
    assert len(digs) == 2
    snap = SENTINEL.snapshot()
    assert "ops.blake2b.words" in snap      # the batch edge's site (PR 27)
    assert snap["ops.blake2b.words"]["calls"] >= 1
    assert obs_metrics.REGISTRY.counter("device.h2d.bytes").value > 0
    assert obs_metrics.REGISTRY.counter("device.d2h.bytes").value >= 128


def test_sentinel_claims_trace_once_across_overlapping_threads(obs_enabled):
    """A cache-hit call overlapping another thread's trace must not be
    counted as a second trace: the claim happens under the stats lock
    against the cache high-water (first updater wins)."""
    import threading

    class FakeJit:
        """Jit-shaped: a shared cache counter, with call B parked
        inside the wrapped call while A's trace grows the cache."""

        def __init__(self):
            self.cache = 0
            self.b_inside = threading.Event()
            self.release_b = threading.Event()

        def _cache_size(self):
            return self.cache

        def __call__(self, x, who="a"):
            if who == "b":
                self.b_inside.set()
                self.release_b.wait(timeout=5)
                return x  # cache HIT: b compiles nothing
            self.cache += 1  # a's call traces
            return x

    fake = FakeJit()
    f = jit_site("test.overlap", fake)
    out = []
    tb = threading.Thread(target=lambda: out.append(f(1, who="b")))
    tb.start()
    assert fake.b_inside.wait(timeout=5)  # b sampled before=0, parked
    f(1, who="a")  # traces: cache 0 -> 1
    fake.release_b.set()  # b returns, sees now=1 > before=0 (stale)
    tb.join(timeout=5)
    snap = SENTINEL.snapshot()["test.overlap"]
    assert snap["calls"] == 2 and snap["traces"] == 1, snap


def test_sentinel_ignores_trace_time_invocations(obs_enabled):
    """A wrapped site called from INSIDE another jitted program runs
    once per OUTER trace, never per execution — counting it would
    report calls == traces for a healthy inner site (and charge the
    outer program's retraces to it)."""
    import jax

    inner = jit_site("test.inner", jax.jit(lambda x: x + 1))
    outer = jax.jit(lambda x: inner(x) * 2)
    for _ in range(3):
        outer(np.ones((4,), np.float32))  # one trace, two cached hits
    assert "test.inner" not in SENTINEL.snapshot()
    # direct (host-side) calls still count
    inner(np.ones((4,), np.float32))
    assert SENTINEL.snapshot()["test.inner"]["calls"] == 1


# -- engine-selection attribution --------------------------------------------


def test_note_engine_records_changes_only(obs_enabled):
    obs_device.note_engine("test.component", "pallas", items=4)
    obs_device.note_engine("test.component", "pallas", items=9)
    obs_device.note_engine("test.component", "native")
    sel = obs_events.EVENTS.events("device.engine.select")
    assert [e["fields"]["engine"] for e in sel] == ["pallas", "native"]


def test_note_engine_key_widens_the_memo(obs_enabled):
    """Per-bucket engine decisions dedup per (component, key): a mix
    straddling the pallas item floor must not flap the memo (ring
    churn), yet each bucket's choice is recorded once."""
    for _ in range(3):
        obs_device.note_engine("test.bucketed", "pallas", key=8)
        obs_device.note_engine("test.bucketed", "xla-scan", key=1)
    sel = obs_events.EVENTS.events("device.engine.select")
    assert [e["fields"]["engine"] for e in sel] == ["pallas", "xla-scan"]


# -- backend-init watchdog ----------------------------------------------------


def test_watchdog_fires_and_bundle_names_stuck_stage(tmp_path, obs_enabled):
    """A stubbed slow init: the deadline fires mid-stage and the
    flight bundle's manifest names the stage it was stuck in (the
    opaque round-5 87s hang, attributed)."""
    obs_flight.FLIGHT.arm(str(tmp_path))
    fired = []
    with BackendInitWatchdog(deadline_s=0.08,
                             on_timeout=fired.append) as wd:
        wd.stage("platform_probe")
        wd.stage("first_device_call")
        time.sleep(0.3)  # stuck "in" first_device_call
    assert wd.fired and fired and fired[0] is wd
    stuck = obs_events.EVENTS.events("backend.init.stuck")
    assert stuck and stuck[0]["fields"]["stage"] == "first_device_call"
    bundles = [d for d in os.listdir(tmp_path) if d.startswith("bundle-")]
    assert len(bundles) == 1 and "backend-init-stuck" in bundles[0]
    man = obs_flight.read_bundle(str(tmp_path / bundles[0]))["manifest"]
    assert man["extra"]["stage"] == "first_device_call"
    assert man["extra"]["elapsed_s"] >= 0.08
    assert [s["stage"] for s in man["extra"]["stages"]] == [
        "platform_probe", "first_device_call"]


def test_watchdog_clean_init_fires_nothing(tmp_path, obs_enabled):
    obs_flight.FLIGHT.arm(str(tmp_path))
    with BackendInitWatchdog(deadline_s=30.0) as wd:
        wd.stage("platform_probe")
        wd.stage("first_compile")
    assert not wd.fired
    assert not [d for d in os.listdir(tmp_path) if d.startswith("bundle-")]
    done = obs_events.EVENTS.events("backend.init.done")
    assert done and done[0]["fields"]["stuck"] is False
    assert obs_events.EVENTS.count("backend.init.stage") == 2
    # the whole init rides one span for the Chrome trace
    from dat_replication_protocol_tpu.obs import tracing as obs_tracing

    assert obs_tracing.SPANS.spans("backend.init")


def test_watchdog_timer_cancelled_after_clean_exit(obs_enabled):
    """No late fire: a watchdog that exited cleanly must not dump after
    its deadline passes."""
    with BackendInitWatchdog(deadline_s=0.05) as wd:
        wd.stage("platform_probe")
    time.sleep(0.12)
    assert not wd.fired
    assert not obs_events.EVENTS.events("backend.init.stuck")


# -- chiplock metrics (ISSUE 5 satellite) ------------------------------------


def test_chiplock_wait_histogram_and_counters(tmp_path, monkeypatch,
                                              obs_enabled):
    from dat_replication_protocol_tpu.utils import chiplock

    monkeypatch.setenv("DAT_CHIP_LOCK", str(tmp_path / "chip.lock"))
    with chiplock.chip_lock(max_wait=1.0) as lease:
        assert lease.held
    h = obs_metrics.REGISTRY.histogram("device.chiplock.wait")
    assert h.count == 1
    assert obs_metrics.REGISTRY.counter("device.chiplock.acquires").value == 1
    assert obs_metrics.REGISTRY.counter("device.chiplock.contended").value == 0


def test_chiplock_contention_counted(tmp_path, monkeypatch, obs_enabled):
    """A held lock (other fd, same file: flock excludes per open-file-
    description) makes the second acquirer wait — the contention
    counter and a nonzero wait observation must record it."""
    import fcntl

    from dat_replication_protocol_tpu.utils import chiplock

    lock = str(tmp_path / "chip.lock")
    monkeypatch.setenv("DAT_CHIP_LOCK", lock)
    fd = os.open(lock, os.O_CREAT | os.O_RDWR, 0o666)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        with chiplock.chip_lock(max_wait=0.2, poll_s=0.05) as lease:
            assert not lease.held  # ran lockless after max_wait
    finally:
        os.close(fd)
    assert obs_metrics.REGISTRY.counter(
        "device.chiplock.contended").value == 1
    assert obs_metrics.REGISTRY.counter(
        "device.chiplock.lockless").value == 1
    assert obs_metrics.REGISTRY.histogram("device.chiplock.wait").count == 1


# -- perf-budget gate ---------------------------------------------------------


def test_perf_check_passes_on_checked_in_snapshot():
    budgets = obs_perf.load_budgets(BUDGETS)
    with open(SNAPSHOT, encoding="utf-8") as f:
        snap = json.load(f)
    rows = obs_perf.check_snapshot(snap, budgets, host_only=True)
    fails = [r for r in rows if r["status"] == "fail"]
    assert not fails, fails
    # and the checks actually RAN (a gate that skips everything passes
    # vacuously)
    assert sum(r["status"] == "ok" for r in rows) >= 4


def test_perf_check_fails_on_doctored_regression():
    budgets = obs_perf.load_budgets(BUDGETS)
    with open(SNAPSHOT, encoding="utf-8") as f:
        snap = json.load(f)
    snap["configs"]["replay"]["value"] /= 1000.0  # the round-2 class
    rows = obs_perf.check_snapshot(snap, budgets, host_only=True)
    bad = obs_perf.find_first_failure(rows)
    assert bad is not None and bad["config"] == "replay"


def test_perf_check_lower_is_better_direction():
    budgets = {"configs": {"resume": {"group": "host", "checks": [
        {"field": "value", "direction": "lower",
         "reference": 0.5, "ratio": 0.05}]}}}
    ok = {"configs": {"resume": {"value": 0.2}}}
    slow = {"configs": {"resume": {"value": 50.0}}}  # > 0.5/0.05
    assert obs_perf.find_first_failure(
        obs_perf.check_snapshot(ok, budgets)) is None
    assert obs_perf.find_first_failure(
        obs_perf.check_snapshot(slow, budgets)) is not None


def test_perf_check_reduced_config_uses_loose_ratio():
    budgets = {"configs": {"hash": {"checks": [
        {"field": "value", "direction": "higher",
         "reference": 100.0, "ratio": 0.5, "reduced_ratio": 0.01}]}}}
    full = {"configs": {"hash": {"value": 10.0}}}          # < 50: fail
    reduced = {"configs": {"hash": {"value": 10.0,
                                    "reduced_config": True}}}  # > 1: ok
    assert obs_perf.find_first_failure(
        obs_perf.check_snapshot(full, budgets)) is not None
    assert obs_perf.find_first_failure(
        obs_perf.check_snapshot(reduced, budgets)) is None


def test_perf_check_malformed_ratio_fails_not_crashes():
    """A zero/negative/non-numeric ratio (reduced_ratio included) is a
    per-check FAIL row, never a ZeroDivisionError traceback."""
    for bad in (0, -1, "x"):
        budgets = {"configs": {"resume": {"checks": [
            {"field": "value", "direction": "lower",
             "reference": 0.5, "ratio": bad}]}}}
        rows = obs_perf.check_snapshot(
            {"configs": {"resume": {"value": 0.1}}}, budgets)
        assert rows[0]["status"] == "fail" and "malformed" in rows[0]["detail"]
    budgets = {"configs": {"hash": {"checks": [
        {"field": "value", "direction": "higher",
         "reference": 1.0, "ratio": 0.5, "reduced_ratio": 0}]}}}
    rows = obs_perf.check_snapshot(
        {"configs": {"hash": {"value": 2.0, "reduced_config": True}}},
        budgets)
    assert rows[0]["status"] == "fail"


def test_perf_check_entry_without_checks_fails_not_passes():
    """A budgeted config whose entry has no (or a mistyped) checks list
    must fail loudly, not pass vacuously."""
    for entry in ({}, {"checks": []}, {"check": [{"field": "value"}]}):
        budgets = {"configs": {"hash": dict(entry)}}
        rows = obs_perf.check_snapshot(
            {"configs": {"hash": {"value": 2.0}}}, budgets)
        assert rows[0]["status"] == "fail"
        assert "no evaluable checks" in rows[0]["detail"]


def test_perf_check_missing_and_errored_configs_fail_unless_optional():
    budgets = {"configs": {
        "hash": {"checks": [{"field": "value", "direction": "higher",
                             "reference": 1.0, "ratio": 0.5}]},
        "cdc": {"optional": True,
                "checks": [{"field": "value", "direction": "higher",
                            "reference": 1.0, "ratio": 0.5}]},
    }}
    snap = {"configs": {"hash": {"error": "boom"}}}
    rows = obs_perf.check_snapshot(snap, budgets)
    by = {r["config"]: r["status"] for r in rows}
    assert by == {"hash": "fail", "cdc": "skip"}


def test_perf_check_cli_exit_codes(tmp_path):
    from dat_replication_protocol_tpu.obs.__main__ import main

    out = io.StringIO()
    rc = obs_perf.run_check(SNAPSHOT, BUDGETS, host_only=True, out=out)
    assert rc == 0 and "within budget" in out.getvalue()
    doctored = tmp_path / "bad.json"
    with open(SNAPSHOT, encoding="utf-8") as f:
        snap = json.load(f)
    snap["configs"]["roundtrip"]["value"] = 1.0
    doctored.write_text(json.dumps(snap))
    assert main(["perf-check", str(doctored), "--budgets", BUDGETS,
                 "--host-only"]) == 1
    assert main(["perf-check", SNAPSHOT, "--budgets", BUDGETS,
                 "--host-only"]) == 0


def test_perf_check_parses_artifact_with_log_noise(tmp_path):
    """Driver logs wrap the artifact line in stderr noise; the parser
    must find the one JSON object line."""
    noisy = tmp_path / "noisy.json"
    with open(SNAPSHOT, encoding="utf-8") as f:
        line = json.dumps(json.load(f))
    noisy.write_text("bench: starting\n" + line + "\nbench: done\n")
    assert obs_perf.run_check(str(noisy), BUDGETS, host_only=True,
                              out=io.StringIO()) == 0


def test_perf_check_prefers_the_configs_object_over_earlier_json(tmp_path):
    """A log that also interleaves OTHER JSON lines (--stats-fd
    periodic snapshots) must still evaluate the bench artifact — the
    last object carrying a 'configs' table, not the first '{' line."""
    noisy = tmp_path / "interleaved.json"
    with open(SNAPSHOT, encoding="utf-8") as f:
        artifact = json.dumps(json.load(f))
    stats_line = json.dumps({"ts": 1.0, "metrics": {"counters": {}}})
    noisy.write_text(stats_line + "\nnoise\n" + artifact + "\ntrailer\n")
    assert obs_perf.run_check(str(noisy), BUDGETS, host_only=True,
                              out=io.StringIO()) == 0


# -- tier-1 gate wiring: the gate exercised end-to-end on a real (tiny)
# host-group bench run (ISSUE 5 satellite: CPU-safe, generous budgets)


def _live_bench_env() -> dict:
    env = dict(os.environ)
    env.update(BENCH_CONFIGS="1,2,6,7,8,9,10,11,12,13,14",
               BENCH_ROUNDTRIPS="50",
               BENCH_DECODE_ROWS="4000", BENCH_REPLAY_ROWS="4000",
               BENCH_RESUME_ROWS="300", BENCH_RESUME_REPS="3",
               BENCH_WIRE_BATCH_ROWS="12288", BENCH_FUSED_MIB="64",
               BENCH_HUB_SESSIONS="6", BENCH_HUB_ROWS="1024",
               BENCH_HUB_BLOB_KIB="128", BENCH_FANOUT_ROWS="1024",
               BENCH_FANOUT_BLOB_KIB="128", BENCH_FANOUT_PEERS="1,8",
               BENCH_FANOUT_STALL_S="0.3", BENCH_RECONCILE_N="6000",
               BENCH_RECONCILE_KS="10,100", BENCH_SNAPSHOT_MIB="4",
               BENCH_SNAPSHOT_JOINERS="4", BENCH_PUMP_MIB="16",
               BENCH_PUMP_SESSIONS="1,4", BENCH_PUMP_REPS="2",
               BENCH_GOSSIP_N="4,8", BENCH_GOSSIP_RECORDS="32",
               BENCH_GOSSIP_DIVERGENCE="8",
               BENCH_DEADLINE="300")
    return env


def _run_quick_bench(env: dict, timeout: int = 280) -> dict:
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick",
         "--metrics"],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return obs_perf._parse_snapshot(r.stdout, "live-bench-stdout")


def _failing_configs(snapshot: dict) -> list:
    budgets = obs_perf.load_budgets(BUDGETS)
    rows = obs_perf.check_snapshot(snapshot, budgets, host_only=True)
    return sorted({r["config"] for r in rows if r["status"] == "fail"})


def test_perf_check_host_only_on_live_quick_bench(tmp_path, monkeypatch):
    snapshot = _run_quick_bench(_live_bench_env())
    failing = _failing_configs(snapshot)
    if failing:
        # one-retry-with-margin rule (ISSUE 15 satellite): a
        # budget-floor miss on the shared tier-1 run can be CI LOAD,
        # not a regression — the whole suite plus this very bench were
        # competing for the 2-core box.  Re-run EXACTLY the failing
        # configs once, in isolation (their own process, nothing else
        # running), and gate on that result.  A true regression fails
        # both runs; only the isolated verdict counts, and only one
        # retry is allowed — "any failure is a real regression" stays
        # true, with the load-flake class carved out mechanically.
        keys = [k for k, (nm, _fn) in bench.BENCHES.items()
                if nm in failing]
        assert keys, f"unrunnable failing configs: {failing}"
        env = _live_bench_env()
        env["BENCH_CONFIGS"] = ",".join(keys)
        rerun = _run_quick_bench(env)
        for name in failing:
            assert name in rerun.get("configs", {}), (
                f"isolated re-run produced no result for {name}")
            snapshot["configs"][name] = rerun["configs"][name]
        still = _failing_configs(snapshot)
        assert not still, (
            f"configs {still} missed their budget floor twice — once "
            f"under load and once in isolation: a real regression")


# -- bench backend_error structure (ISSUE 5 satellite) ------------------------


def test_backend_error_carries_stage_and_elapsed(obs_enabled, monkeypatch):
    """The in-process init's failure record: message always, and —
    with telemetry on — the last init stage entered, its elapsed time
    and the device telemetry subset."""
    monkeypatch.setitem(bench._METRICS, "on", True)
    obs_events.emit("backend.init.stage", stage="first_device_call",
                    elapsed_s=87.3)
    err = bench._backend_error(RuntimeError("Unable to initialize backend"))
    assert err["message"] == "RuntimeError: Unable to initialize backend"
    assert err["stage"] == "first_device_call"
    assert err["elapsed_s"] == 87.3
    assert set(err["telemetry"]) == {"counters", "gauges", "histograms"}
    monkeypatch.setitem(bench._METRICS, "on", False)
    dark = bench._backend_error(RuntimeError("x"))
    assert dark == {"message": "RuntimeError: x", "stage": None,
                    "elapsed_s": None}


def test_backend_init_failure_reports_stage_and_exits_nonzero():
    """A run forced onto a nonexistent platform fails (fast) in the ONE
    process that runs the configs: non-zero exit, a structured
    backend_error whose stage is from the real ladder, every requested
    config an error — and no child process to fall back to."""
    import subprocess
    import sys

    env = dict(os.environ, BENCH_PLATFORM="no_such_platform",
               BENCH_CONFIGS="3", BENCH_DEADLINE="120")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick",
         "--metrics"],
        capture_output=True, text=True, timeout=150, env=env, cwd=REPO)
    assert r.returncode == 1, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    err = out["backend_error"]
    assert "no_such_platform" in err["message"]
    assert err["stage"] in obs_device.INIT_STAGES
    assert out["backend"] is None and out["device"] is None
    assert "error" in out["configs"]["hash"]
    assert out["value"] is None  # no figure under the device metric's name


def test_emit_carries_structured_backend_error(monkeypatch, capsys):
    monkeypatch.setattr(bench, "_emitted", False)
    monkeypatch.setitem(bench._state, "configs", {})
    monkeypatch.setitem(
        bench._state, "backend_error",
        {"message": "backend init hung (> 87s)",
         "stage": "first_device_call", "elapsed_s": 87.0})
    bench._emit()
    out = json.loads(capsys.readouterr().out)
    assert out["backend_error"]["stage"] == "first_device_call"
    assert out["backend_error"]["elapsed_s"] == 87.0


def test_digest_pipeline_counts_stream_bytes(obs_enabled):
    """submit_stream carries a blob-heavy session's dominant volume;
    device.submit.bytes must account it (catalog contract)."""
    from dat_replication_protocol_tpu.backend.tpu_backend import (
        DigestPipeline, _HostStream,
    )

    pipe = DigestPipeline(hash_batch=lambda ps: [b"\0" * 32 for _ in ps])
    s = _HostStream()
    s.update(b"x" * 1000)
    got = []
    pipe.submit_stream(s, got.append)
    pipe.submit(b"y" * 10, got.append)
    pipe.flush()
    assert len(got) == 2
    assert obs_metrics.REGISTRY.counter("device.submit.bytes").value == 1010
    assert obs_metrics.REGISTRY.counter("device.submit.items").value == 2
    # a stream's bytes are hashed on the host and never reach the device
    assert obs_metrics.REGISTRY.counter(
        "device.host.stream.bytes").value == 1000


def test_bench_trace_export_resets_engine_memo(tmp_path, obs_enabled):
    """The per-config ring clear must also reset the engine-select
    memo, or every config after the first loses its attribution."""
    obs_device.note_engine("test.memo", "xla-scan")
    bench._export_config_trace("memo_probe", str(tmp_path))
    assert obs_events.EVENTS.events("device.engine.select") == []
    obs_device.note_engine("test.memo", "xla-scan")  # same engine again
    sel = obs_events.EVENTS.events("device.engine.select")
    assert len(sel) == 1  # re-emitted into the fresh capture


def test_device_telemetry_subset_filters_prefixes(obs_enabled):
    obs_metrics.REGISTRY.counter("device.h2d.bytes").inc(7)
    obs_metrics.REGISTRY.counter("decoder.bytes").inc(9)
    obs_metrics.REGISTRY.histogram("device.chiplock.wait").observe(0.5)
    obs_metrics.REGISTRY.histogram("decoder.dispatch.seconds").observe(0.1)
    sub = bench._device_telemetry_subset()
    assert sub["counters"].get("device.h2d.bytes") == 7
    assert "decoder.bytes" not in sub["counters"]
    # the one device-path histogram rides the subset too
    assert sub["histograms"]["device.chiplock.wait"]["count"] == 1
    assert "decoder.dispatch.seconds" not in sub["histograms"]
