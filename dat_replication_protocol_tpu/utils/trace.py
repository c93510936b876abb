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
  ``--stats-fd`` snapshot.  With the gate off the fields are dropped
  and the bound factory's annotation is returned directly.
* :func:`annotation` — the first third alone, for a site whose lit
  record another recorder already keeps (the edge loop's
  ``LoopProfiler``).

Two rules keep the idle-gap attribution honest (OBSERVABILITY.md): no
span brackets a wait on another thread or on a peer, and none brackets
a session, connection or loop lifetime — sites are per batch, per pump
slab or per lit loop turn, never per item or per frame.

JAX is imported lazily: the session layer must stay importable (and
fast) in processes that never touch a device.
"""
# datlint: disable-file=obs-discipline  — this module IS span plumbing:
# it forwards caller-supplied span names into jax.profiler and the obs
# span ring by design; its callers are the greppable sites.

from __future__ import annotations

import sys
import threading

from ..obs import tracing as _obs_tracing
from ..obs.metrics import OBS as _OBS, histogram as _histogram


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


# span name -> its `span.<name>.seconds` histogram, bound at the
# name's first lit use (a dict read under the GIL afterwards; the
# registry keeps registrations across reset(), so handles stay valid)
_span_hists: dict = {}

# the `batch` field of the innermost lit span that carries one, per
# thread: what a stage opened inside it inherits
_tls = threading.local()


class _JoinedSpan:
    """The profiler annotation plus the two lit readings of the same
    region: one obs span record (``src="jax"``, parent link, fields,
    inherited ``batch``) and one ``span.<name>.seconds`` observation."""

    __slots__ = ("_span", "_inner", "_outer_batch")

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
        return self

    def __exit__(self, *exc):
        try:
            return self._inner.__exit__(*exc) or False
        finally:
            span_ = self._span
            span_.__exit__(*exc)
            _tls.batch = self._outer_batch
            if span_.dur is not None:
                hist = _span_hists.get(span_.name)
                if hist is None:
                    hist = _span_hists[span_.name] = _histogram(
                        f"span.{span_.name}.seconds")
                hist.observe(span_.dur)


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
