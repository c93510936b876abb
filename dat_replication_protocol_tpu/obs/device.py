"""Device-path telemetry: recompile sentinel, device gauges, engines.

PRs 3-4 made the *host* session datapath observable; the device path —
Pallas kernels, the DigestPipeline, mesh programs — stayed dark: the
recompile hazards behind the round-2 ~2000x CDC regression
(SURVEY.md §5) were guarded only by code comments.  This module
extends the same zero-dependency ``obs`` discipline (hoisted
``OBS.on`` gate, literal names, bounded rings) down to the device
boundary.  JAX is never imported at module level — the session layer
must stay importable (and hang-proof) in device-less processes.

Two parts:

* **Recompile sentinel** — :func:`jit_site` wraps a jitted callable
  with a named call-site.  Per call (gate on) it detects whether the
  call TRACED (a fresh specialization) vs hit the jit cache, via the
  callable's own lowering-cache size when it exposes one
  (``PjitFunction._cache_size``) and an arg-shape-signature closure
  otherwise.  Every trace records a ``device.jit.trace`` event with
  the site and the arg-shape signature; :data:`SENTINEL` aggregates
  per-site calls/traces; :class:`RecompileBudget` flags any site that
  recompiles more than N times per process — the unbucketed-batch-size
  failure mode ``ops/blake2b.py`` buckets against (jit specializes per
  (B, nblocks); an unbucketed stream recompiles every distinct count,
  minutes each on the CPU scanned path).
* **Device gauges / engine attribution** — :func:`sample_device_gauges`
  snapshots live-buffer count and device bytes-in-use at phase
  boundaries (only when a backend is ALREADY initialized: the sampler
  must never be the thing that wedges); :func:`note_engine` records
  ``device.engine.select`` events when a routing layer's
  pallas/native/host choice changes.

Catalog and budget: OBSERVABILITY.md (device-telemetry section).
"""
from __future__ import annotations

import sys
import threading
from typing import Callable, Optional

from .events import emit as _emit
from .metrics import OBS as _OBS
from .metrics import counter as _counter
from .metrics import gauge as _gauge

__all__ = [
    "SENTINEL",
    "BUCKETS",
    "BucketTable",
    "JitSentinel",
    "RecompileBudget",
    "jit_site",
    "note_engine",
    "sample_device_gauges",
    "watch_compile_events",
    "DEFAULT_RECOMPILE_BUDGET",
]

# jit-cache traffic across ALL sites (per-site split: SENTINEL.snapshot)
_M_JIT_CALLS = _counter("device.jit.calls")
_M_JIT_TRACES = _counter("device.jit.traces")
_G_LIVE_BUFFERS = _gauge("device.mem.live_buffers")
_G_BYTES_IN_USE = _gauge("device.mem.bytes_in_use")
_G_PEAK_BYTES = _gauge("device.mem.peak_bytes_in_use")
# jax's own compile accounting, mirrored by watch_compile_events():
# persistent-cache traffic and seconds spent tracing/lowering/compiling
_M_CACHE_REQUESTS = _counter("device.compile.cache.requests")
_M_CACHE_HITS = _counter("device.compile.cache.hits")
_M_CACHE_MISSES = _counter("device.compile.cache.misses")
_G_TRACE_SECONDS = _gauge("device.compile.trace_seconds")
_G_BACKEND_SECONDS = _gauge("device.compile.backend_seconds")

# traces per site before the sentinel flags it: generous enough for the
# legitimate power-of-two bucket ladder (a handful of (B, nblocks)
# shapes per engine), small enough to catch an unbucketed stream within
# its first dozen batches instead of after a 2000x regression ships
DEFAULT_RECOMPILE_BUDGET = 8

# shape-signature sets are bounded: a pathological site (the exact bug
# class the sentinel hunts) would otherwise grow the set forever — past
# the cap every unseen signature still COUNTS as a trace, it just is
# not retained
_MAX_RETAINED_SIGS = 256


def _sig_of(v) -> object:
    shape = getattr(v, "shape", None)
    if shape is not None:
        return (tuple(shape), str(getattr(v, "dtype", "")))
    if isinstance(v, (bool, int, float, str, bytes, type(None))):
        return v
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(_sig_of(x) for x in v)
    return type(v).__name__


def _signature(args: tuple, kwargs: dict) -> tuple:
    """Hashable abstract signature of one call: shapes/dtypes for
    array-likes, values for static scalars — the same axes jit
    specializes on, so a new signature approximates a new trace."""
    sig = tuple(_sig_of(a) for a in args)
    if kwargs:
        sig += tuple((k, _sig_of(kwargs[k])) for k in sorted(kwargs))
    return sig


def _sig_str(sig: tuple) -> str:
    """Compact display form for events ("(8, 16)u32" style)."""

    def one(p) -> str:
        if isinstance(p, tuple) and len(p) == 2 and isinstance(p[0], tuple):
            return f"{p[0]}{p[1]}"
        return repr(p)

    return ",".join(one(p) for p in sig)


class _SiteStats:
    """Per-site aggregate; shared by every wrapper registered under one
    name (e.g. one mesh program per mesh, one site name)."""

    __slots__ = ("name", "lock", "calls", "traces", "sigs", "flagged",
                 "last_signature")

    def __init__(self, name: str):
        self.name = name
        self.lock = threading.Lock()
        self.calls = 0
        self.traces = 0
        self.sigs: set = set()
        self.flagged = False
        self.last_signature: Optional[str] = None


class JitSentinel:
    """Process-global per-site trace/call accounting."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._sites: dict[str, _SiteStats] = {}

    def _stats(self, name: str) -> _SiteStats:
        with self._lock:
            st = self._sites.get(name)
            if st is None:
                st = self._sites[name] = _SiteStats(name)
            return st

    def snapshot(self) -> dict:
        """``{site: {"calls": n, "traces": n}}`` for every site that has
        been CALLED (registered-but-idle sites are omitted)."""
        with self._lock:
            sites = list(self._sites.values())
        out = {}
        for st in sites:
            with st.lock:
                if st.calls:
                    out[st.name] = {"calls": st.calls, "traces": st.traces}
        return out

    def over_budget(self, limit: int = DEFAULT_RECOMPILE_BUDGET) -> list[dict]:
        """Sites whose trace count exceeds ``limit``, worst first."""
        out = []
        for name, rec in self.snapshot().items():
            if rec["traces"] > limit:
                out.append({"site": name, **rec})
        out.sort(key=lambda r: -r["traces"])
        return out

    def reset_for_tests(self) -> None:
        """Zero every site's VALUES in place, keeping the registrations
        (and the stats objects module-level ``jit_site`` wrappers hold)
        intact — clearing the dict would orphan those handles, exactly
        the hazard ``Registry.reset`` documents."""
        with self._lock:
            sites = list(self._sites.values())
        for st in sites:
            with st.lock:
                st.calls = 0
                st.traces = 0
                st.sigs.clear()
                st.flagged = False
                st.last_signature = None


SENTINEL = JitSentinel()


class RecompileBudget:
    """The enforceable face of the sentinel: ``check()`` returns every
    site recompiling more than ``limit`` times this process (empty =
    healthy), for callers that want a hard gate rather than events."""

    def __init__(self, limit: int = DEFAULT_RECOMPILE_BUDGET,
                 sentinel: JitSentinel = SENTINEL):
        if limit < 1:
            raise ValueError("recompile budget must be >= 1")
        self.limit = limit
        self._sentinel = sentinel

    def check(self) -> list[dict]:
        return self._sentinel.over_budget(self.limit)

    def ok(self) -> bool:
        return not self.check()


_trace_state_clean: Optional[Callable[[], bool]] = None


def _outside_jax_trace() -> bool:
    """True when we are NOT inside a jax trace.  Sites wrapped by the
    sentinel are also called from INSIDE other jitted programs (mesh
    steps call ``blake2b_packed``, ``diff_root_guided_packed`` calls
    ``diff_root_guided``); those invocations run once per OUTER trace
    and never per execution, so counting them would report
    calls == traces — the exact pathology signature the sentinel
    exists to flag — for perfectly healthy inner sites.  Bound lazily:
    jax is never imported here, only observed if already loaded.  The
    binding is jax 0.9.0's (``jax._src.core.trace_state_clean``); a jax
    without it raises here rather than miscounting every inner site."""
    global _trace_state_clean
    fn = _trace_state_clean
    if fn is None:
        if "jax" not in sys.modules:
            return True  # no jax in the process: nothing can be tracing
        from jax._src import core as _jax_core  # noqa: PLC0415

        fn = _trace_state_clean = _jax_core.trace_state_clean
    return fn()


class _JitSite:
    """The wrapper :func:`jit_site` returns.  Disabled path: one gate
    attribute load, then straight through to the wrapped callable.
    Trace-time invocations (the wrapper called while an OUTER program
    traces) bypass accounting entirely — see :func:`_outside_jax_trace`.
    Unknown attributes (``lower``, ``clear_cache``, ...) delegate to the
    wrapped jit so the site stays a drop-in."""

    __slots__ = ("_fn", "_stats", "_cache_size", "_cache_seen")

    def __init__(self, name: str, fn: Callable):
        self._fn = fn
        self._stats = SENTINEL._stats(name)
        cs = getattr(fn, "_cache_size", None)
        self._cache_size = cs if callable(cs) else None
        # high-water of the jit cache size this wrapper has accounted
        # for: the trace CLAIM happens under the stats lock against it,
        # so two threads overlapping one trace charge it exactly once
        self._cache_seen: Optional[int] = None

    @property
    def site(self) -> str:
        return self._stats.name

    @property
    def __wrapped__(self) -> Callable:
        return self._fn

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, *args, **kwargs):
        if not _OBS.on:
            return self._fn(*args, **kwargs)
        if not _outside_jax_trace():
            return self._fn(*args, **kwargs)
        cs = self._cache_size
        before = cs() if cs is not None else None
        out = self._fn(*args, **kwargs)
        sig = None
        st = self._stats
        with st.lock:
            st.calls += 1
            if cs is not None:
                # a trace happened iff the cache grew DURING this call
                # (growth outside the sampling window — e.g. trace-time
                # bypassed invocations compiling under an outer jit —
                # never counts), and is CLAIMED against the high-water
                # under the lock: a cache-hit call overlapping another
                # thread's trace sees the growth already claimed and
                # stays silent.  (Two DISTINCT concurrent traces can
                # collapse to one count — undercount, never a false
                # recompile alarm.)
                now = cs()
                seen = self._cache_seen
                traced = now > before and (seen is None or now > seen)
                if seen is None or now > seen:
                    self._cache_seen = now
                if traced:
                    sig = _signature(args, kwargs)
            else:
                sig = _signature(args, kwargs)
                traced = sig not in st.sigs
            if sig is not None and len(st.sigs) < _MAX_RETAINED_SIGS:
                st.sigs.add(sig)
            if traced:
                st.traces += 1
                traces = st.traces
                st.last_signature = _sig_str(sig)
                flag = traces > DEFAULT_RECOMPILE_BUDGET and not st.flagged
                if flag:
                    st.flagged = True
            else:
                traces = st.traces
                flag = False
        _M_JIT_CALLS.inc()
        if traced:
            _M_JIT_TRACES.inc()
            _emit("device.jit.trace", site=st.name, signature=_sig_str(sig),
                  traces=traces)
            if flag:
                # the unbucketed-batch-size failure mode, caught live:
                # one event per site per process, however long it runs
                _emit("device.jit.recompile_budget", site=st.name,
                      traces=traces, budget=DEFAULT_RECOMPILE_BUDGET,
                      signature=_sig_str(sig))
        return out


def jit_site(name: str, fn: Callable) -> _JitSite:
    """Register ``fn`` (a jitted callable) as the named call-site and
    return the sentinel wrapper.  ``name`` is a dot-separated literal
    (the obs-discipline rule enforces greppability at call sites)."""
    return _JitSite(name, fn)


# -- engine-selection attribution ---------------------------------------------

# last engine noted per component: the select event records CHANGES,
# not every dispatch — a steady pipeline emits one line, a flapping
# router shows every flap
_engine_lock = threading.Lock()
_engine_last: dict[str, str] = {}


def note_engine(component: str, engine: str, key=None, **fields) -> None:
    """Record ``device.engine.select`` when ``component``'s routed
    engine changes (pallas / xla-scan / native / hashlib / ...).  Call
    sites guard with ``if _OBS.on:``; this function does not re-check
    the gate.

    ``key`` widens the change-only memo for decisions that are
    legitimately per-shape: the feed layer (``batch.feed``) picks its
    engine per block-count BUCKET, and a payload mix straddling its
    chunk floor would otherwise flap pallas<->xla-scan on every
    dispatch, churning the bounded event ring with noise.  (The served
    batch edge, ``ops.blake2b``, has one engine per backend and no
    key.)"""
    memo = component if key is None else (component, key)
    with _engine_lock:
        if _engine_last.get(memo) == engine:
            return
        _engine_last[memo] = engine
    _emit("device.engine.select", component=component, engine=engine,
          **fields)


def reset_engine_notes() -> None:
    """Forget the change-only memo so the NEXT dispatch re-emits every
    component's ``device.engine.select``.  Capture boundaries call this
    alongside clearing the event/span rings (the test fixture) — a
    cleared ring with a warm memo would
    silently drop engine attribution from every later capture."""
    with _engine_lock:
        _engine_last.clear()


# -- blake2b batch-edge bucket accounting -------------------------------------


class BucketTable:
    """Process-global traffic of the blake2b batch edge per (engine,
    block-count bucket): dispatches, real items, and ``padded_items`` —
    the DECLARED row count each dispatch was staged at
    (``ops.blake2b.batch_rows``: one per slot width up to 4 KiB, a few
    doubling ones for wider slots).  The engine is the backend's
    (``pallas`` on a TPU, ``xla-scan`` elsewhere), so this table says
    which kernel a traffic mix reached and what its padding cost —
    bounded cardinality (two engines x power-of-two block counts).
    Call sites guard with ``if _OBS.on:``."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: dict[tuple[str, int], list[int]] = {}

    def note(self, engine: str, nblocks: int, items: int,
             padded_items: int) -> None:
        with self._lock:
            row = self._rows.setdefault((engine, nblocks), [0, 0, 0])
            row[0] += 1
            row[1] += items
            row[2] += padded_items

    def snapshot(self) -> dict:
        """``{"<engine>:<nblocks>": {"dispatches", "items",
        "padded_items"}}`` (JSON-able)."""
        with self._lock:
            rows = sorted(self._rows.items())
        return {f"{engine}:{nb}": {"dispatches": d, "items": i,
                                   "padded_items": p}
                for (engine, nb), (d, i, p) in rows}

    def reset_for_tests(self) -> None:
        with self._lock:
            self._rows.clear()


BUCKETS = BucketTable()


# -- device memory gauges -----------------------------------------------------


def sample_device_gauges() -> bool:
    """Update ``device.mem.live_buffers`` / ``device.mem.bytes_in_use``
    from an ALREADY-initialized jax backend; returns True when a sample
    was taken.  Never initializes a backend itself: a sampler must not
    be what causes a slow first init — an uninitialized process samples
    nothing."""
    if not _OBS.on:
        return False
    if "jax" not in sys.modules:
        return False
    # jax is loaded or being loaded: this import joins an import in
    # flight on another thread (never a half-initialised module) and
    # starts none
    import jax  # noqa: PLC0415
    from jax._src import xla_bridge  # noqa: PLC0415

    if not xla_bridge.backends_are_initialized():
        return False
    _G_LIVE_BUFFERS.set(float(len(jax.live_arrays())))
    # memory_stats() is None on backends that keep no allocator stats
    # (the CPU client): the live-buffer gauge is the whole sample there
    stats = jax.local_devices()[0].memory_stats() or {}
    if "bytes_in_use" in stats:
        _G_BYTES_IN_USE.set(float(stats["bytes_in_use"]))
    if "peak_bytes_in_use" in stats:
        _G_PEAK_BYTES.set(float(stats["peak_bytes_in_use"]))
    return True


# -- compile accounting -------------------------------------------------------

_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": _M_CACHE_REQUESTS,
    "/jax/compilation_cache/cache_hits": _M_CACHE_HITS,
    # jax records a miss when it WRITES an entry: a program under the
    # cache's compile-time floor is a request that is neither
    "/jax/compilation_cache/cache_misses": _M_CACHE_MISSES,
}
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": _G_TRACE_SECONDS,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": _G_TRACE_SECONDS,
    # on a persistent-cache hit this is the retrieval time
    "/jax/core/compile/backend_compile_duration": _G_BACKEND_SECONDS,
}
_watching_compiles = False


def _on_jax_event(event: str, **_kw) -> None:
    m = _CACHE_EVENTS.get(event)
    if m is not None and _OBS.on:
        m.inc()


def _on_jax_duration(event: str, duration: float, **_kw) -> None:
    g = _DURATION_EVENTS.get(event)
    if g is not None and _OBS.on:
        g.inc(duration)


def watch_compile_events() -> None:
    """Mirror jax's compile accounting (``jax.monitoring``) into the
    registry: persistent-cache requests / hits / misses as
    ``device.compile.cache.*`` and the seconds jax spent tracing,
    lowering (``device.compile.trace_seconds``) and compiling or
    fetching executables (``device.compile.backend_seconds``) — set-up
    time a snapshot can tell apart from run time.  Imports jax; call
    from entry points that are about to use it.  Idempotent."""
    global _watching_compiles
    if _watching_compiles:
        return
    import jax.monitoring  # noqa: PLC0415

    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _watching_compiles = True
