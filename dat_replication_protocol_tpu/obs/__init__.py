"""obs — session telemetry: metrics core + structured event log.

The reference's only observability is three passive counters
(reference: encode.js:51-53, decode.js:68-70).  This package is the
host-visible telemetry layer for everything the session stack does at
runtime — retries, stalls, replay bytes, watcher-vs-poll wakeups —
exactly the datapath an offload-style deployment no longer steps
through (PAPERS: *Reliable Replication Protocols on SmartNICs*).

Deliberately zero-dependency and flat (stdlib only, no JAX, no numpy):
the layer must be importable and near-free in every process that
touches the session stack, including the stripped CI image
(PAPERS: *Simplicity Scales*).

The parts:

* :mod:`.metrics` — Counters / Gauges / Histograms in a process-global
  registry behind ONE hoisted enable gate (``OBS.on``): the disabled
  path at an instrumentation site is a single attribute load, the same
  trick as ``_fastpath_gate``.  ``to_prom_text`` renders a snapshot in
  Prometheus text exposition.
* :mod:`.events` — a bounded-ring structured event log (monotonic ts +
  seq) with an optional fd/JSONL sink, for session *lifecycle*:
  connect, checkpoint, resume, backoff, replay, stall, truncation,
  ProtocolError.
* :mod:`.tracing` — wire-offset-correlated spans (ISSUE 4): nestable
  ``trace_span`` contexts, per-frame ``trace_instant`` tags keyed on
  the byte offset each frame starts at, and Chrome trace-event export
  with the JAX profiler annotations of :mod:`..utils.trace` joined in.
* :mod:`.flight` — the flight recorder: on any structured
  ProtocolError or reconnect exhaustion, an armed recorder atomically
  dumps a post-mortem bundle (rings + registry + checkpoint + active
  fault plans) for offline attribution.
* :mod:`.device` — the device boundary (ISSUE 5): the recompile
  sentinel (:func:`~.device.jit_site` wrappers counting traces vs
  cache hits per jit call-site, with a :class:`~.device.RecompileBudget`),
  device memory gauges, and engine-selection attribution.
* :mod:`.wirecost` — the wire cost plane (ISSUE 20): a per-link byte
  ledger attributing EVERY wire byte to a frame class (change,
  change_batch, blob, reconcile, snapshot, framing-overhead) at the
  existing choke points, with derived goodput/overhead/amplification
  watermarks, the ``obs fleet`` cost-matrix join, and the offline
  ``obs costdoctor`` auditor.  The headline invariant: the ledger
  EXACTLY TILES the wire (residual vs transport ground truth is 0 at
  convergence).
* :mod:`.watermarks` / :mod:`.http` / :mod:`.fleet` — the fleet plane
  (ISSUE 11): wire-position cursors exported as labeled gauges
  (``append − parsed`` is exact replication lag in bytes; append
  marks make lag-in-seconds clock-free), a read-only stdlib-HTTP
  scrape endpoint (sidecar ``--obs-http``: ``/metrics`` ``/snapshot``
  ``/healthz`` ``/events``), and the N-target aggregator behind
  ``obs fleet`` (TTY dashboard, declarative SLO gate).

Offline CLI: ``python -m dat_replication_protocol_tpu.obs`` merges N
peers' JSONL logs into one causally-ordered timeline (``timeline``),
converts logs/bundles to Perfetto-loadable traces (``export-trace``),
pretty-prints bundles (``dump``), and joins live replica targets into
per-link lag (``fleet``).

The fault injector (:mod:`..session.faults`) is the layer's
correctness oracle: it emits ground-truth ``fault.*`` events for every
fault it injects, and the conformance sweep
(tests/test_obs_conformance.py) asserts the session layers' telemetry
agrees — chaos and telemetry must tell the same story.

Catalog, schema, overhead budget: OBSERVABILITY.md.
"""

from __future__ import annotations

from .device import (
    SENTINEL,
    JitSentinel,
    RecompileBudget,
    jit_site,
    note_engine,
    sample_device_gauges,
)
from .events import EVENTS, EventLog, emit
from .flight import FLIGHT, FlightRecorder, read_bundle
from .metrics import (
    OBS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    disable,
    enable,
    gauge,
    histogram,
    snapshot,
    to_prom_text,
)
from .tracing import (
    SPANS,
    SpanLog,
    attach_jsonl_sink,
    export_chrome_trace,
    to_chrome_trace,
    trace_instant,
    trace_span,
)
from .watermarks import WATERMARKS, WatermarkBoard, link_lag

__all__ = [
    "OBS",
    "REGISTRY",
    "EVENTS",
    "SPANS",
    "FLIGHT",
    "EventLog",
    "SpanLog",
    "FlightRecorder",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "to_prom_text",
    "emit",
    "enable",
    "disable",
    "trace_span",
    "trace_instant",
    "to_chrome_trace",
    "export_chrome_trace",
    "attach_jsonl_sink",
    "read_bundle",
    "SENTINEL",
    "JitSentinel",
    "RecompileBudget",
    "jit_site",
    "note_engine",
    "sample_device_gauges",
    "WATERMARKS",
    "WatermarkBoard",
    "link_lag",
]
