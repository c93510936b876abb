"""Zero-dependency metrics core: counters, gauges, histograms.

Design constraints (ISSUE 3 acceptance, OBSERVABILITY.md):

* **Disabled path is one attribute load.**  Instrumentation sites hold
  a pre-bound metric handle (created at module import) and guard with
  ``if _OBS.on:`` — no registry lookup, no dict allocation, no call at
  all when telemetry is off.  ``OBS`` is a one-slot object so the
  check compiles to LOAD_GLOBAL + LOAD_ATTR + POP_JUMP, the same
  hoisted-gate trick as ``_fastpath_gate``.
* **The gate is a runtime LATCH, not a per-call env read.**  Unlike
  ``DAT_FASTPATH_DISABLE`` (a behavior fork that must stay re-readable,
  see the env-cache-policy rule), the obs gate exists precisely so hot
  paths do NOT pay an environ lookup: ``DAT_OBS=1`` seeds the initial
  state, and :func:`enable` / :func:`disable` flip it at runtime
  (the sidecar's ``--stats-fd`` does, tests do).
* **Enabled path favors correctness over nanoseconds.**  Every mutate
  takes the metric's lock: a Python ``x += 1`` is a read-modify-write
  that can lose increments across threads, and the session stack is
  aggressively multi-threaded (pumps, ack threads, the sidecar).  The
  overhead budget test bounds only the disabled path.
* **Snapshots are plain dicts** (JSON-able as-is): the sidecar's
  ``--stats-fd`` dumps and the conformance oracle consume the same
  shape.

Histograms keep BOTH fixed-bucket counts (cheap, mergeable) and a
fixed-size ring of recent observations (wraparound overwrite) so
``snapshot()`` can report approximate quantiles of the *recent* window
without unbounded memory.
"""

from __future__ import annotations

import math
import os
import re
import threading
from typing import Optional, Sequence

__all__ = [
    "OBS",
    "CPU_CLOCK_EVERY",
    "cpu_clock_visit",
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "to_prom_text",
    "enable",
    "disable",
]


class _Gate:
    """The hoisted enable gate.  Instrumentation sites read ``OBS.on``
    and nothing else.

    ``frames`` is the second slot, read only INSIDE an ``if OBS.on:``
    block: it lights the per-frame wire-offset instants
    (``decoder.frame`` / ``encoder.frame``), whose only readers are the
    offline timeline CLI over ``--trace-jsonl`` files and the flight
    recorder.  A supervisor that only scrapes snapshots
    (``--stats-fd`` / ``--obs-http``) lights ``on`` alone and pays no
    dict, lock and ring append per frame."""

    __slots__ = ("on", "frames")

    def __init__(self) -> None:
        self.on = False
        self.frames = False


OBS = _Gate()

# The lit second clock — a thread's CPU seconds beside a region's wall
# seconds (utils.trace spans, session.pump.recv_fetch, the edge loop's
# read phase) — is read on ONE VISIT IN THIS MANY of each region,
# starting with the first.  ``time.thread_time()`` is a real system
# call with the interpreter lock held: ~0.3 us on bare Linux, but 6 us
# alone and 20 us beside busy threads on a sandboxed host (gVisor, the
# TPU machines'), where read at every visit it took 12% off the lit
# rate of the busiest cell (PERF.md section 6, PR 37).  The wall clock
# stays on every visit; a reader compares the two MEANS.
CPU_CLOCK_EVERY = 8


def cpu_clock_visit(n: int) -> bool:
    """Whether visit ``n`` (0, 1, 2 ...) of a region takes the CPU
    clock: the first does, and one in ``CPU_CLOCK_EVERY`` over any
    run of visits, picked by a Weyl sequence (the fractional part of
    ``n`` x the golden ratio) so that no period of the traffic — eight
    sessions read in turn, a pack every second span — lines up with
    the choice."""
    return (n * 0x9E3779B1) & 0xFFFFFFFF < (1 << 32) // CPU_CLOCK_EVERY


def enable(frames: bool = True) -> None:
    """Turn telemetry on process-wide (idempotent).  ``frames=False``
    lights the gate without the per-frame instants, and leaves them as
    they are if an earlier call lit them."""
    OBS.on = True
    if frames:
        OBS.frames = True


def disable() -> None:
    OBS.on = False
    OBS.frames = False


def _seed_gate_from_env() -> None:
    # initial state only — enable()/disable() own the gate afterwards
    # (a latch by design: the whole point of the hoisted gate is that
    # hot paths never pay an environ read; see module docstring)
    if os.environ.get("DAT_OBS", "") not in ("", "0"):
        OBS.on = True
        OBS.frames = True


_seed_gate_from_env()


class Counter:
    """Monotonic counter.  ``inc`` under the lock: increments from pump
    threads, ack threads, and the sidecar's emitter must not be lost."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = v

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        with self._lock:
            self._value -= n

    @property
    def value(self) -> float:
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0.0


# Default buckets span the session stack's latency range: sub-us gate
# checks up through multi-second backoff sleeps.  Upper edges are
# INCLUSIVE (observe(x) lands in the first bucket with x <= edge), with
# an implicit +inf overflow bucket.
DEFAULT_BUCKETS = (
    1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0,
)

DEFAULT_RING = 256


class Histogram:
    """Fixed buckets + a ring buffer of recent raw observations.

    The buckets give cheap, mergeable distribution counts; the ring
    gives approximate quantiles over the most recent ``ring`` samples
    (older samples are overwritten — wraparound, bounded memory).
    """

    __slots__ = ("name", "buckets", "_lock", "_counts", "_count", "_sum",
                 "_ring", "_ring_n")

    def __init__(self, name: str,
                 buckets: Sequence[float] = DEFAULT_BUCKETS,
                 ring: int = DEFAULT_RING):
        if list(buckets) != sorted(buckets) or len(set(buckets)) != len(
                tuple(buckets)):
            raise ValueError("histogram buckets must be sorted and unique")
        if ring < 1:
            raise ValueError("ring size must be >= 1")
        self.name = name
        self.buckets = tuple(float(b) for b in buckets)
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.buckets) + 1)  # +1: +inf overflow
        self._count = 0
        self._sum = 0.0
        self._ring: list[float] = [0.0] * ring
        self._ring_n = 0  # total observations ever; ring index = n % len

    def observe(self, v: float) -> None:
        with self._lock:
            i = 0
            buckets = self.buckets
            n = len(buckets)
            while i < n and v > buckets[i]:
                i += 1
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            ring = self._ring
            ring[self._ring_n % len(ring)] = v
            self._ring_n += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def quantile(self, q: float) -> Optional[float]:
        """Approximate ``q``-quantile (0..1) over the ring window, or
        None before the first observation.  Nearest-rank on a sorted
        copy — snapshot-time cost, not observe-time cost."""
        if not 0.0 <= q <= 1.0:
            raise ValueError("quantile must be in [0, 1]")
        with self._lock:
            n = min(self._ring_n, len(self._ring))
            if n == 0:
                return None
            window = sorted(self._ring[:n])
        rank = min(n - 1, max(0, math.ceil(q * n) - 1))
        return window[rank]

    def _reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.buckets) + 1)
            self._count = 0
            self._sum = 0.0
            self._ring_n = 0

    def _snapshot(self) -> dict:
        with self._lock:
            counts = list(self._counts)
            count = self._count
            total = self._sum
            n = min(self._ring_n, len(self._ring))
            window = sorted(self._ring[:n])

        def q(frac: float) -> Optional[float]:
            if not window:
                return None
            rank = min(len(window) - 1, max(0, math.ceil(frac * len(window)) - 1))
            return window[rank]

        return {
            "count": count,
            "sum": total,
            "buckets": [[le, c] for le, c in zip(self.buckets, counts)]
            + [["+inf", counts[-1]]],
            "p50": q(0.50),
            "p90": q(0.90),
            "p99": q(0.99),
        }


class Registry:
    """Name -> metric, process-global.  Get-or-create is idempotent so
    any module can hoist a handle at import without ordering concerns;
    a name registered twice with a different TYPE is a programming
    error and raises.

    **Collectors** are the bounded-cardinality answer to per-entity
    metrics (ISSUE 8: per-session hub telemetry): registering one
    counter/gauge per session key would grow the registry forever —
    sessions come and go, metric registrations never do.  A collector
    is a callable the owner registers ONCE; at ``snapshot()`` time it
    returns ``{"counters": {...}, "gauges": {...}}`` for the entities
    *currently alive*, and those entries are merged into the snapshot
    (labeled names — ``hub.session.parked_bytes{session=k}`` — keep
    them distinguishable from registered metrics).  Dead entities
    simply stop appearing; nothing leaks.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, object] = {}
        self._collectors: dict[str, object] = {}

    def _get(self, name: str, cls, *args, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                # ``cls`` is always one of THIS module's metric classes
                # (Counter/Gauge/Histogram — the three public wrappers
                # are the only callers): a cheap pure constructor, not
                # user code, so constructing under the registry lock
                # cannot block or re-enter.
                # datlint: allow-blocking-under-lock(callback)
                m = cls(name, *args, **kwargs)
                self._metrics[name] = m
            elif type(m) is not cls:
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {cls.__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_BUCKETS,
                  ring: int = DEFAULT_RING) -> Histogram:
        h = self._get(name, Histogram, buckets, ring)
        # parameter drift is the same silent catalog fork the type
        # check above guards: a second registration with different
        # edges would quietly get the FIRST caller's buckets
        if h.buckets != tuple(float(b) for b in buckets) \
                or len(h._ring) != ring:
            raise ValueError(
                f"histogram {name!r} already registered with different "
                f"buckets/ring")
        return h

    def register_collector(self, name: str, fn) -> None:
        """Attach a snapshot-time collector (see class docstring).
        ``fn()`` must return a dict with optional ``counters`` /
        ``gauges`` sections; re-registering a name replaces the old
        collector (the hub re-registers on restart)."""
        with self._lock:
            self._collectors[name] = fn

    def unregister_collector(self, name: str, fn=None) -> None:
        """Remove a collector.  Pass the registered ``fn`` to make the
        removal owner-checked: a replaced collector's OLD owner closing
        late must not delete the NEW owner's live entry (the hub
        rolling-restart pattern)."""
        with self._lock:
            if fn is None or self._collectors.get(name) is fn:
                self._collectors.pop(name, None)

    def snapshot(self) -> dict:
        """Plain-dict view of every registered metric (JSON-able)."""
        with self._lock:
            metrics = list(self._metrics.values())
            collectors = list(self._collectors.values())
        out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        for m in metrics:
            if isinstance(m, Counter):
                out["counters"][m.name] = m.value
            elif isinstance(m, Gauge):
                out["gauges"][m.name] = m.value
            elif isinstance(m, Histogram):
                out["histograms"][m.name] = m._snapshot()
        for fn in collectors:
            try:
                # collectors are snapshot-grade attribute reads (the
                # hub/fanout/edge `_collect` contract) — best-effort,
                # absorbed by the except arm below
                # datlint: allow-callback-escape
                contributed = fn()
            except Exception:
                # a dying collector (hub mid-close) must not take the
                # whole snapshot down — the registered metrics are the
                # contract, collector entries are best-effort extras
                continue
            for section in ("counters", "gauges"):
                out[section].update(contributed.get(section, {}))
        return out

    def reset(self) -> None:
        """Zero every metric's VALUE, keeping registrations (and the
        handles instrumentation sites hoisted) intact — per-test
        isolation.  Collectors ARE dropped: they hold references into
        live owner state (a hub), and a collector surviving its test
        would leak that state into the next snapshot."""
        with self._lock:
            metrics = list(self._metrics.values())
            self._collectors.clear()
        for m in metrics:
            m._reset()


REGISTRY = Registry()


def counter(name: str) -> Counter:
    """Get-or-create a counter in the process-global registry."""
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str, buckets: Sequence[float] = DEFAULT_BUCKETS,
              ring: int = DEFAULT_RING) -> Histogram:
    return REGISTRY.histogram(name, buckets, ring)


def snapshot() -> dict:
    return REGISTRY.snapshot()


# -- Prometheus text exposition ----------------------------------------------

_PROM_SANITIZE = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    """Catalog name -> Prometheus metric name: dots become underscores,
    everything namespaced under ``dat_`` (``decoder.blob.bytes`` ->
    ``dat_decoder_blob_bytes``)."""
    return "dat_" + _PROM_SANITIZE.sub("_", name)


def _prom_series(name: str) -> str:
    """Full series name for one snapshot entry.  Labeled collector
    entries (``hub.session.parked_bytes{session=k1}``) become proper
    Prometheus label sets (``dat_hub_session_parked_bytes{session="k1"}``);
    plain names pass through :func:`_prom_name`."""
    if "{" not in name or not name.endswith("}"):
        return _prom_name(name)
    base, _, labels = name[:-1].partition("{")
    pairs = []
    for part in labels.split(","):
        k, _, v = part.partition("=")
        # exposition-format escaping for label values: backslash,
        # double-quote, and (defensively — producers reject them at
        # their boundary) literal newlines
        v = v.replace("\\", "\\\\").replace('"', '\\"') \
             .replace("\n", "\\n")
        pairs.append(f'{_PROM_SANITIZE.sub("_", k.strip())}="{v}"')
    return _prom_name(base) + "{" + ",".join(pairs) + "}"


def _prom_num(v) -> str:
    if isinstance(v, float):
        if v != v:  # NaN
            return "NaN"
        if v in (float("inf"), float("-inf")):
            return "+Inf" if v > 0 else "-Inf"
        return repr(v)
    return str(v)


def to_prom_text(snap: Optional[dict] = None) -> str:
    """Prometheus text-exposition (v0.0.4) rendering of a registry
    snapshot (default: the live registry).  Counters and gauges map
    directly; histograms emit CUMULATIVE ``_bucket{le=...}`` series
    (the snapshot stores per-bucket counts) plus ``_sum``/``_count``,
    with the implicit overflow bucket as ``le="+Inf"``.  The sidecar's
    ``--stats-fd`` emitter renders this with ``--stats-format prom``."""
    if snap is None:
        snap = REGISTRY.snapshot()
    lines: list[str] = []

    def emit_section(section: str, kind: str) -> None:
        # one TYPE line per metric NAME, however many label sets the
        # collectors contribute — a second TYPE line for the same name
        # makes the whole scrape invalid exposition
        typed: set = set()
        for name, v in sorted(snap.get(section, {}).items()):
            n = _prom_series(name)
            base = n.partition("{")[0]
            if base not in typed:
                typed.add(base)
                lines.append(f"# TYPE {base} {kind}")
            lines.append(f"{n} {_prom_num(v)}")

    emit_section("counters", "counter")
    emit_section("gauges", "gauge")
    for name, h in sorted(snap.get("histograms", {}).items()):
        n = _prom_name(name)
        lines.append(f"# TYPE {n} histogram")
        cum = 0
        for le, count in h["buckets"]:
            cum += count
            label = "+Inf" if le == "+inf" else _prom_num(float(le))
            lines.append(f'{n}_bucket{{le="{label}"}} {cum}')
        lines.append(f"{n}_sum {_prom_num(float(h['sum']))}")
        lines.append(f"{n}_count {h['count']}")
    return "\n".join(lines) + "\n"
