"""Causal tracing: wire-offset-correlated spans + Chrome trace export.

The wire protocol already carries a perfect causal key: the byte
offset every frame starts at (the same offset ``Decoder.checkpoint()``
resumes from).  This module is the span half of the observability
layer (ISSUE 4): nestable, thread-correct named spans recorded into a
bounded ring, plus zero-duration *instants* the session layer uses to
tag every encoder frame emission and decoder frame dispatch with its
wire offset — end-to-end tracing with no wire-format change.

* :class:`trace_span` — ``with trace_span("reconnect.attempt", ...):``
  context manager.  Nesting is tracked per-thread (a threadlocal parent
  stack), so spans opened concurrently on pump/ack/sidecar threads
  never corrupt each other's parent links.  Gated on the same hoisted
  ``OBS.on`` gate as the metrics layer.
* :func:`trace_instant` — the frame-tagging hot path: one record, zero
  duration.  Call sites guard with ``if _OBS.on:`` so the disabled
  path stays one attribute load (OBSERVABILITY.md's budget); this
  function does NOT re-check the gate.
* :data:`SPANS` — the process-global bounded span ring (an
  :class:`~.events.EventLog` subclass: same wraparound accounting and
  the same atomic JSONL sink discipline).
* :func:`to_chrome_trace` / :func:`export_chrome_trace` — Chrome
  trace-event JSON (Perfetto / chrome://tracing loadable).  JAX
  profiler annotations recorded through :mod:`...utils.trace` ride in
  like any other span (field ``src="jax"``), so host wire phases and
  device dispatch phases share one timeline.
* :func:`attach_jsonl_sink` — mirror events AND spans into one JSONL
  file through a shared lock (lines never interleave); the offline
  timeline CLI (``python -m dat_replication_protocol_tpu.obs``)
  consumes exactly these files.

Span record shape (one JSON object per line on a sink)::

    {"seq": 12, "ts": 103.2, "dur": 0.0018, "span": "reconnect.attempt",
     "id": 7, "parent": 3, "tid": 139923, "fields": {"offset": 4711}}

Frame instants (lit by the gate's second slot, ``OBS.frames``: a
snapshot scraper does not pay for them) use ``span`` names
``encoder.frame`` / ``decoder.frame``
(and ``decoder.frame.run`` for a native bulk-dispatch run) with fields
``offset`` (wire offset of the frame's first header byte), ``wire_len``
(header + payload bytes), ``kind`` (``change``/``blob``) and, for runs,
``frames``.  Both peers compute offsets from the same framing rules
(:func:`~..wire.framing.header_len`), so a sender's emission instant
and the receiver's dispatch instant for one frame carry the SAME
offset — that equality is the whole causal-correlation contract.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Optional

from .events import EVENTS, EventLog
from .metrics import OBS

__all__ = [
    "SPANS",
    "SpanLog",
    "trace_span",
    "trace_instant",
    "to_chrome_trace",
    "export_chrome_trace",
    "attach_jsonl_sink",
]

DEFAULT_SPAN_CAPACITY = 4096


class SpanLog(EventLog):
    """Bounded ring of span records — EventLog's ring/sink machinery
    with span-shaped records (``span`` instead of ``event``, plus
    ``dur``/``id``/``parent``/``tid``)."""

    def record(self, name: str, ts: float, dur: float, span_id: int,
               parent: Optional[int], tid: int, fields: dict) -> None:
        """Append one finished span.  NOT gated: the producing context
        managers / call sites own the ``OBS.on`` check (a span that
        STARTED while the gate was on still records if the gate flips
        mid-span)."""
        self._append({"seq": 0, "ts": ts, "dur": dur, "span": name,
                      "id": span_id, "parent": parent, "tid": tid,
                      "fields": fields})

    def spans(self, name: Optional[str] = None) -> list[dict]:
        """Snapshot of retained span records, oldest first."""
        with self._lock:
            records = list(self._ring)
        if name is None:
            return records
        return [r for r in records if r.get("span") == name]


SPANS = SpanLog(DEFAULT_SPAN_CAPACITY)

# span ids are process-wide so parent links stay unambiguous across
# threads; count().__next__ is atomic under the GIL
_span_ids = itertools.count(1)

_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class trace_span:
    """Nestable named span; thread-correct via a threadlocal parent
    stack.  Cheap no-op while the gate is off (one gate check at enter,
    one slot check at exit) — hot per-frame sites use
    :func:`trace_instant` behind their own ``if _OBS.on:`` guard
    instead, keeping the disabled path at one attribute load."""

    __slots__ = ("name", "fields", "dur", "_t0", "_id", "_parent", "_on")

    def __init__(self, name: str, **fields):
        self.name = name
        self.fields = fields

    def __enter__(self) -> "trace_span":
        # seconds, once a recorded span has ended (utils.trace.span
        # feeds its histogram from it); None for a span that was dark
        self.dur = None
        if not OBS.on:
            self._on = False
            return self
        self._on = True
        st = _stack()
        self._id = next(_span_ids)
        self._parent = st[-1] if st else None
        st.append(self._id)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._on:
            st = _stack()
            if st and st[-1] == self._id:
                st.pop()
            fields = self.fields
            if exc_type is not None:
                # a span that ended by exception says so — post-mortem
                # timelines need the failing phase, not just the error
                fields = dict(fields, error=exc_type.__name__)
            self.dur = time.monotonic() - self._t0
            SPANS.record(self.name, self._t0, self.dur, self._id,
                         self._parent, threading.get_ident(), fields)
        return False


def trace_instant(name: str, **fields) -> None:
    """Zero-duration span (a Chrome 'instant') — the frame-tagging hot
    path.  Call sites guard with ``if _OBS.on:``; this function does
    not re-check the gate."""
    st = getattr(_tls, "stack", None)
    SPANS.record(name, time.monotonic(), 0.0, next(_span_ids),
                 st[-1] if st else None, threading.get_ident(), fields)


# -- Chrome trace-event export ------------------------------------------------


def to_chrome_trace(spans: Optional[list] = None,
                    events: Optional[list] = None) -> dict:
    """Chrome trace-event JSON from span + event records (defaults:
    the live ``SPANS`` / ``EVENTS`` rings).  Loadable by Perfetto and
    chrome://tracing: spans with duration become complete events
    (``ph: "X"``), frame instants and log events become instants
    (``ph: "i"``).  Timestamps/durations are microseconds as the format
    requires; JAX annotation spans (``src="jax"``) are joined in like
    any other span."""
    if spans is None:
        spans = SPANS.spans()
    if events is None:
        events = EVENTS.events()
    pid = os.getpid()
    trace_events = []
    for r in spans:
        if "span" not in r:
            continue
        args = dict(r.get("fields") or {})
        args["seq"] = r.get("seq", 0)
        if r.get("parent") is not None:
            args["parent"] = r["parent"]
        ev = {
            "name": r["span"],
            "ts": r.get("ts", 0.0) * 1e6,
            "pid": pid,
            "tid": r.get("tid", 0),
            "args": args,
        }
        if r.get("dur"):
            ev["ph"] = "X"
            ev["dur"] = r["dur"] * 1e6
        else:
            ev["ph"] = "i"
            ev["s"] = "t"  # thread-scoped instant
        trace_events.append(ev)
    for e in events:
        if "event" not in e:
            continue
        trace_events.append({
            "name": e["event"],
            "ph": "i",
            "s": "p",  # process-scoped instant
            "ts": e.get("ts", 0.0) * 1e6,
            "pid": pid,
            "tid": 0,
            "args": dict(e.get("fields") or {}, seq=e.get("seq", 0)),
        })
    trace_events.sort(key=lambda ev: ev["ts"])
    return {
        "traceEvents": trace_events,
        "displayTimeUnit": "ms",
        "metadata": {"pid": pid},
    }


def export_chrome_trace(path: str, spans: Optional[list] = None,
                        events: Optional[list] = None) -> str:
    """Write :func:`to_chrome_trace` to ``path`` atomically (tmp +
    rename); returns the path."""
    doc = to_chrome_trace(spans, events)
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return path


# -- shared JSONL sink --------------------------------------------------------


class _LockedLineFile:
    """A ``write(str)`` sink shared by the event and span logs: one
    lock across both, so their lines can never interleave mid-record
    (each log's own ``_sink_lock`` only serializes within that log)."""

    def __init__(self, f):
        self._f = f
        self._lock = threading.Lock()

    def write(self, s: str) -> None:
        with self._lock:
            # serializing this file I/O is this lock's entire job (one
            # line per record across BOTH logs); it is a leaf lock —
            # nothing else is ever acquired under it.  Callers holding
            # other locks are not excused by this marker.
            # datlint: allow-blocking-under-lock(file-io)
            self._f.write(s)
            # datlint: allow-blocking-under-lock(file-io)
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            self._f.close()


def attach_jsonl_sink(path: str) -> _LockedLineFile:
    """Mirror every subsequent event AND span as JSONL into ``path``
    (append mode) through one shared lock.  Returns the sink — call
    ``close()`` after detaching.  The offline timeline CLI consumes
    these files: one per peer."""
    sink = _LockedLineFile(open(path, "a", encoding="utf-8"))
    EVENTS.attach_sink(sink)
    SPANS.attach_sink(sink)
    return sink
