"""Device-path telemetry (ISSUE 5): recompile sentinel, engine
attribution, the digest pipeline's byte counters.

The sentinel's acceptance shape: a deliberately shape-UNSTABLE jit
site is counted trace-by-trace (and flagged over budget), while a
bucketed/shape-stable one stays silent after its first specialization.
"""

import time

import numpy as np

from dat_replication_protocol_tpu.obs import device as obs_device
from dat_replication_protocol_tpu.obs import events as obs_events
from dat_replication_protocol_tpu.obs import metrics as obs_metrics
from dat_replication_protocol_tpu.obs.device import (
    RecompileBudget,
    SENTINEL,
    jit_site,
)


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


def test_reset_engine_notes_makes_the_next_dispatch_emit_again(obs_enabled):
    """A capture boundary clears the event ring; the change-only memo
    must go with it, or a later capture loses its attribution."""
    obs_device.note_engine("test.memo", "xla-scan")
    obs_events.EVENTS.clear()
    obs_device.reset_engine_notes()
    obs_device.note_engine("test.memo", "xla-scan")  # same engine again
    assert len(obs_events.EVENTS.events("device.engine.select")) == 1


# -- the digest pipeline's counters -------------------------------------------


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
