"""Stage spans: one call at each layer boundary, read three ways.

The reference has no tracing at all — only passive byte/frame counters
(reference: encode.js:51-53, decode.js:68-70).  At device scale that is
not enough: round 2 shipped a ~2000x CDC regression that a single trace
would have localized in minutes (the cost was H2D staging, not the
kernel).  This module is the hook every host-side stage of the device
path is bracketed with::

    with span("digest.pack", items=k, bytes=b): ...

* :func:`span` — named stage.  (1) ALWAYS a
  ``jax.profiler.TraceAnnotation(name)``: it lands on the profiler's
  host plane on the device trace's own clock, which is what attributes
  the device's idle gaps to host stages; ~ns when no trace is active,
  so call sites leave it on unconditionally.  (2) With the obs gate on,
  one record in the obs span ring (``obs.tracing.SPANS``, field
  ``src="jax"``) with its parent link and the caller's fields; a span
  opened inside one that carries ``batch`` inherits it, so every stage
  of one device batch shares the pipeline's dispatch ordinal.  (3) With
  the gate on, the duration is added to the histogram
  ``span.<name>.seconds``, whose ``count``/``sum`` ride every
  ``--stats-fd`` snapshot, and on one visit in
  ``CPU_CLOCK_EVERY`` of each name, the first included, the THREAD'S
  CPU seconds inside the span (``time.thread_time()``, read inside the
  wall clock's two reads) go on the record as field ``cpu`` and into
  the twin histogram ``span.<name>.cpu_seconds``.  Mean wall minus
  mean cpu is the span's off-CPU time, and rule 1 below says what
  that can be.  With the gate off the fields are dropped, no clock is
  read and the bound factory's annotation is returned directly.
* :func:`annotation` — the first third alone, for a site whose lit
  record another recorder already keeps (the edge loop's
  ``LoopProfiler``).

Two rules keep the idle-gap attribution honest (OBSERVABILITY.md): no
span brackets a wait on another thread or on a peer, and none brackets
a session, connection or loop lifetime — sites are per batch, per pump
slab or per lit loop turn, never per item or per frame.  The first
rule's corollary: inside a stage span a thread is off its CPU only
while it waits to take the interpreter lock back or is blocked inside
the runtime.  ``digest.d2h_wait`` (the one sanctioned wait for the
device: off-CPU nearly all of it) and ``digest.pack`` (no blocking
runtime call: off-CPU is the lock alone) calibrate the reading in every
lit run.

JAX is imported lazily: the session layer must stay importable (and
fast) in processes that never touch a device.
"""
# datlint: disable-file=obs-discipline  — this module IS span plumbing:
# it forwards caller-supplied span names into jax.profiler and the obs
# span ring by design; its callers are the greppable sites.

from __future__ import annotations

import itertools
import sys
import threading
from time import thread_time as _thread_time

from ..obs import tracing as _obs_tracing
from ..obs.metrics import OBS as _OBS, \
    cpu_clock_visit as _cpu_clock_visit, histogram as _histogram


class _NullSpan:
    def __init__(self, *_a, **_k):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# Lazily bound span factory: jax.profiler.TraceAnnotation, or _NullSpan
# when JAX is unavailable.  Bound ONCE at first use (the _fastpath_gate
# trick): span() sits on dispatch hot paths, and re-attempting the
# import on every call costs ~1.8us of import machinery per span even
# on the cache-hit path.  Availability of jax cannot change mid-process
# (unlike an env-var gate), so a permanent bind is safe;
# _reset_span_binding_for_tests() exists for test isolation only.
_span_factory = None


def _bind_span_factory():
    global _span_factory
    try:
        from jax.profiler import TraceAnnotation as factory
    except Exception:
        factory = _NullSpan
    _span_factory = factory
    return factory


def _reset_span_binding_for_tests() -> None:
    global _span_factory
    _span_factory = None


# span name -> (its `span.<name>.seconds` histogram, its
# `span.<name>.cpu_seconds` histogram, the count of its lit visits),
# bound together at the name's first lit use (a dict read under the
# GIL afterwards; the registry keeps registrations across reset(), so
# handles stay valid)
_span_hists: dict = {}


def _bind_span_hists(name: str) -> tuple:
    bound = _span_hists[name] = (
        _histogram(f"span.{name}.seconds"),
        _histogram(f"span.{name}.cpu_seconds"), itertools.count())
    return bound


# the `batch` field of the innermost lit span that carries one, per
# thread: what a stage opened inside it inherits
_tls = threading.local()


class _JoinedSpan:
    """The profiler annotation plus the lit readings of the same
    region: one obs span record (``src="jax"``, parent link, fields,
    inherited ``batch``) and one ``span.<name>.seconds`` observation;
    on a visit that takes the second clock (module docstring), also
    the thread's ``cpu`` seconds on the record and one
    ``span.<name>.cpu_seconds`` observation.  The CPU clock is read
    INSIDE the wall clock's reads, so a record's ``cpu`` passes its
    ``dur`` by the clock's grain at most."""

    __slots__ = ("_span", "_inner", "_outer_batch", "_hists", "_cpu0")

    def __init__(self, name: str, inner, fields: dict | None = None):
        self._span = _obs_tracing.trace_span(name, src="jax",
                                             **(fields or {}))
        self._inner = inner

    def __enter__(self):
        fields = self._span.fields
        outer = self._outer_batch = getattr(_tls, "batch", None)
        if "batch" in fields:
            _tls.batch = fields["batch"]
        elif outer is not None:
            fields["batch"] = outer
        self._span.__enter__()
        try:
            self._inner.__enter__()
        except BaseException:
            # unwind the obs span: a raising jax annotation means the
            # with-statement never runs __exit__, and an unpopped id
            # would corrupt the thread's span-parent stack for good
            self._span.__exit__(*sys.exc_info())
            _tls.batch = outer
            raise
        name = self._span.name
        hists = self._hists = _span_hists.get(name) \
            or _bind_span_hists(name)
        self._cpu0 = _thread_time() \
            if _cpu_clock_visit(next(hists[2])) else None
        return self

    def __exit__(self, *exc):
        cpu0 = self._cpu0
        cpu = None if cpu0 is None else _thread_time() - cpu0
        try:
            return self._inner.__exit__(*exc) or False
        finally:
            span_ = self._span
            if cpu is not None:
                span_.fields["cpu"] = cpu
            span_.__exit__(*exc)
            _tls.batch = self._outer_batch
            if span_.dur is not None:
                self._hists[0].observe(span_.dur)
                if cpu is not None:
                    self._hists[1].observe(cpu)


def annotation(name: str):
    """The profiler annotation alone, gate or no gate; inert if jax is
    unavailable."""
    factory = _span_factory or _bind_span_factory()
    return factory(name)


def span(name: str, **fields):
    """Named stage (see module docstring).  ``fields`` reach the obs
    span ring only — the profiler sees the bare name, so a trace
    reduction can match it."""
    if _OBS.on:
        return _JoinedSpan(name, annotation(name), fields)
    return annotation(name)
