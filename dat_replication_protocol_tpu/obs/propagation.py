"""Mesh convergence plane: gossip-exchange provenance + divergence
watermarks (ISSUE 19).

The PR 15 gossip mesh converges, but until this module it converged as
a telemetry black box: the fleet plane showed rounds-behind and
quarantine counts, yet nobody could answer *which link, which round,
which record* was holding convergence back.  This is the mesh analogue
of the PR 18 event-loop flight deck — one record shape, one board, no
new protocol machinery ("Simplicity Scales"):

* every :func:`~..cluster.node.gossip_exchange` (both directions, live
  and sim) calls :func:`record_exchange`, which emits ONE structured
  ``gossip.exchange`` span — peer, round, role
  (``initiator``/``responder``), decoded diff size, wire bytes, wall
  seconds, outcome (``converged``/``progress``/``transport``/
  ``corruption``/``refused``) plus the delivered digest prefixes the
  offline meshdoctor rebuilds the propagation tree from;
* the process-global :data:`PROPAGATION` board keeps per-(replica,
  peer) **divergence watermarks** — the diff the exchange's own peel
  result measured, in records and in repair wire bytes — and exports
  them as labeled gauges (``cluster.divergence{replica=,peer=}``,
  ``cluster.divergence_bytes{replica=,peer=}``) through the PR 8
  collector machinery, alongside a ``cluster.frontier{replica=}``
  content-digest gauge (a 52-bit equality FINGERPRINT of the digest —
  two replicas are converged iff the gauges are equal; the magnitude
  means nothing);
* :meth:`PropagationBoard.snapshot` is the ``propagation`` section the
  sidecar's ``--stats-fd`` / ``/snapshot`` records carry — the fleet
  aggregator's mesh-matrix join input (per-pair divergence, per-link
  last-successful-exchange age, exchange-seconds quantiles).

Dark-path discipline (the PR 18 contract): NOTHING here runs unless
``OBS.on`` — the exchange engine forks to a dark twin that the
bytecode-level test proves references no symbol of this module, so the
disabled cost of the whole plane is one attribute load.

Event vocabulary for the offline doctor (``obs meshdoctor``):

``gossip.mesh``
    one per sim/mesh start: ``n``, ``seed``, ``bound``
    (:meth:`~..cluster.sim.ClusterSim.rounds_bound` — the budget the
    doctor's rounds-bound-exceeded flag checks against);
``gossip.hold``
    a replica acquired records OUTSIDE an exchange (initial state,
    snapshot bootstrap, feed drain): ``replica``, ``round``,
    ``digests`` (hex16 prefixes) — the propagation tree's provenance
    roots;
``gossip.exchange`` (span)
    one per exchange per direction; ``delivered`` /
    ``delivered_peer`` carry the digest prefixes each side absorbed;
``gossip.frontier``
    change-only: a replica's content digest moved (``replica``,
    ``round``, ``digest``, ``records``) — the doctor derives the
    convergence round from the LAST frontier change per replica.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Optional

from .events import emit as _emit
from .metrics import REGISTRY as _REGISTRY, OBS as _OBS
from .tracing import SPANS as _SPANS, _span_ids

__all__ = [
    "PROPAGATION",
    "PropagationBoard",
    "record_exchange",
    "note_hold",
    "note_mesh",
    "note_frontier",
    "digest_prefixes",
    "frontier_fingerprint",
    "OUTCOMES",
]

# the exchange outcome vocabulary (OBSERVABILITY.md "Mesh convergence
# plane"): converged (peel found an empty diff), progress (diff moved),
# transport (retryable, no state changed), corruption (structured
# protocol failure — suspicion accrues), refused (quarantine refusal)
OUTCOMES = ("converged", "progress", "transport", "corruption",
            "refused")

# digest prefix length (hex chars) carried by hold/exchange records:
# 64 bits of the 256-bit canonical digest — collision-safe for any
# realistic mesh while keeping JSONL lines bounded
_DIGEST_HEX = 16

# recent exchange wall-seconds window for the p50/p99 export (board-
# owned, NOT a registry histogram: reset_for_tests must drop it with
# the board, and the fleet SLO gate reads the quantile directly)
_SECONDS_RING = 512


def digest_prefixes(digests) -> list:
    """Canonical digest rows (the ``(n, 32)`` uint8 array every
    :class:`~..runtime.reconcile_driver.RatelessReplica` exposes) as
    the hex16 prefixes provenance records carry."""
    return [bytes(d).hex()[:_DIGEST_HEX] for d in digests]


def frontier_fingerprint(digest_hex: str) -> float:
    """The ``cluster.frontier`` gauge value: the content digest's first
    52 bits as a float (exact in IEEE-754 — an EQUALITY fingerprint,
    compared never ordered)."""
    return float(int(digest_hex[:13] or "0", 16))


class PropagationBoard:
    """Process-global per-link exchange provenance + divergence
    watermarks.  See module docstring; the instance is
    :data:`PROPAGATION`."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # datlint: guarded-by(self._lock): self._links, self._frontier, self._seconds
        # (replica, peer) -> the last-exchange record for that directed
        # pair, monotonic-stamped
        self._links: dict[tuple, dict] = {}
        # replica -> last frontier record (content digest + count)
        self._frontier: dict[str, dict] = {}
        self._seconds: deque = deque(maxlen=_SECONDS_RING)
        self._collector_fn = self._collect

    # -- recording -----------------------------------------------------------

    def record(self, replica: str, peer: str, *, role: str, rnd: int,
               outcome: str, seconds: float, diff: Optional[int] = None,
               wire_bytes: int = 0, repair_bytes: int = 0,
               error: Optional[str] = None) -> None:
        """Fold one exchange (one direction's view) into the board.
        ``diff`` is the peel result (records in the symmetric
        difference) — only known on completed exchanges; a failed
        exchange keeps the pair's previous divergence watermark (the
        divergence did not heal, and fabricating 0 would read as
        converged — the direction an SLO gate must never err in)."""
        now = time.monotonic()
        with self._lock:
            rec = self._links.setdefault((replica, peer), {
                "role": role, "round": 0, "outcome": None,
                "divergence_records": None, "divergence_bytes": None,
                "wire_bytes": 0, "seconds": 0.0, "exchanges": 0,
                "failures": 0, "error": None, "_mono": now,
                "_ok_mono": None,
            })
            rec["role"] = role
            rec["round"] = int(rnd)
            rec["outcome"] = outcome
            rec["seconds"] = float(seconds)
            rec["wire_bytes"] = int(wire_bytes)
            rec["error"] = error
            rec["exchanges"] += 1
            rec["_mono"] = now
            if outcome in ("converged", "progress"):
                rec["_ok_mono"] = now
                rec["divergence_records"] = int(diff or 0)
                rec["divergence_bytes"] = int(repair_bytes)
            else:
                rec["failures"] += 1
            if outcome != "refused":
                self._seconds.append(float(seconds))
        _REGISTRY.register_collector("propagation", self._collector_fn)

    def note_frontier(self, replica: str, digest_hex: str,
                      records: int, rnd: int) -> bool:
        """Change-only frontier tracking: returns True when the
        replica's content digest actually moved (the caller emits the
        ``gossip.frontier`` event only then)."""
        with self._lock:
            prev = self._frontier.get(replica)
            if prev is not None and prev["digest"] == digest_hex:
                return False
            self._frontier[replica] = {"digest": digest_hex,
                                       "records": int(records),
                                       "round": int(rnd)}
        _REGISTRY.register_collector("propagation", self._collector_fn)
        return True

    # -- export --------------------------------------------------------------

    def exchange_p99(self) -> Optional[float]:
        """p99 exchange wall seconds over the recent window (None
        before the first completed exchange) — the fleet SLO's
        ``max_exchange_p99_s`` input."""
        return self._quantile(0.99)

    def _quantile(self, q: float) -> Optional[float]:
        with self._lock:
            window = sorted(self._seconds)
        if not window:
            return None
        rank = min(len(window) - 1,
                   max(0, math.ceil(q * len(window)) - 1))
        return window[rank]

    def snapshot(self) -> dict:
        """The ``propagation`` section of the sidecar snapshot record
        (JSON-able): per-directed-link last-exchange state with ages on
        THIS process's monotonic clock, per-replica frontier, and the
        exchange-seconds quantiles."""
        now = time.monotonic()
        with self._lock:
            links = {f"{r}->{p}": dict(rec)
                     for (r, p), rec in self._links.items()}
            frontier = {k: dict(v) for k, v in self._frontier.items()}
        for rec in links.values():
            rec["age_s"] = round(now - rec.pop("_mono"), 6)
            ok = rec.pop("_ok_mono")
            rec["last_success_age_s"] = (round(now - ok, 6)
                                         if ok is not None else None)
        return {
            "monotonic": now,
            "links": links,
            "frontier": frontier,
            "exchange_seconds": {
                "count": len(self._seconds),
                "p50": self._quantile(0.50),
                "p99": self._quantile(0.99),
            },
        }

    def _collect(self) -> dict:
        """Registry collector: the divergence watermarks as labeled
        gauges (bounded cardinality — one entry per live directed
        pair), plus the frontier equality fingerprints."""
        gauges: dict = {}
        with self._lock:
            links = [(k, dict(v)) for k, v in self._links.items()]
            frontier = list(self._frontier.items())
        for (replica, peer), rec in links:
            if rec["divergence_records"] is None:
                continue  # no completed peel yet: unknown, not zero
            gauges[f"cluster.divergence{{replica={replica},peer={peer}}}"] \
                = float(rec["divergence_records"])
            gauges["cluster.divergence_bytes"
                   f"{{replica={replica},peer={peer}}}"] = float(
                rec["divergence_bytes"])
        for replica, rec in frontier:
            gauges[f"cluster.frontier{{replica={replica}}}"] = \
                frontier_fingerprint(rec["digest"])
        return {"gauges": gauges}

    def reset_for_tests(self) -> None:
        """Drop every link, frontier, and the seconds window (process-
        global state — test isolation is explicit, the conftest
        ``obs_enabled`` contract)."""
        with self._lock:
            self._links.clear()
            self._frontier.clear()
            self._seconds.clear()


PROPAGATION = PropagationBoard()


# -- the instrumentation surface (callers hold the OBS.on gate) --------------


def record_exchange(replica: str, peer: str, *, role: str, rnd: int,
                    outcome: str, seconds: float,
                    diff: Optional[int] = None, wire_bytes: int = 0,
                    repair_bytes: int = 0, delivered=(),
                    delivered_peer=(), t0: Optional[float] = None,
                    error: Optional[str] = None) -> None:
    """One direction's view of one gossip exchange: board watermarks +
    the ``gossip.exchange`` span the meshdoctor consumes.

    ``delivered`` are the digest prefixes THIS replica absorbed,
    ``delivered_peer`` the ones it shipped to ``peer`` — the edges of
    the per-record propagation tree.  ``t0`` is the exchange's start
    on this process's monotonic clock (defaults to now − seconds).
    Callers gate with ``if _OBS.on:`` (dark-path discipline); the span
    ring additionally ignores records while the gate is off."""
    PROPAGATION.record(replica, peer, role=role, rnd=rnd,
                       outcome=outcome, seconds=seconds, diff=diff,
                       wire_bytes=wire_bytes, repair_bytes=repair_bytes,
                       error=error)
    start = t0 if t0 is not None else time.monotonic() - seconds
    fields = {
        "replica": replica, "peer": peer, "role": role, "round": int(rnd),
        "outcome": outcome, "wire_bytes": int(wire_bytes),
        "repair_bytes": int(repair_bytes),
        "seconds": round(float(seconds), 6),
    }
    if diff is not None:
        fields["diff"] = int(diff)
    if delivered:
        fields["delivered"] = list(delivered)
    if delivered_peer:
        fields["delivered_peer"] = list(delivered_peer)
    if error is not None:
        fields["error"] = error
    _SPANS.record("gossip.exchange", start, float(seconds),
                  next(_span_ids), None, threading.get_ident(), fields)


def note_hold(replica: str, digests, rnd: int = 0) -> None:
    """A replica acquired ``digests`` outside any exchange (initial
    state, snapshot bootstrap, broadcast-feed drain) — provenance roots
    for the meshdoctor's orphaned-digest check.  ``digests`` are hex16
    prefixes (:func:`digest_prefixes`)."""
    _emit("gossip.hold", replica=replica, round=int(rnd),
          digests=list(digests))


def note_mesh(n: int, seed: int, bound: int) -> None:
    """One mesh/sim start: the doctor's ground-truth frame (replica
    count, seed, and the bounded round budget convergence is judged
    against)."""
    _emit("gossip.mesh", n=int(n), seed=int(seed), bound=int(bound))


def note_frontier(replica: str, digest_hex: str, records: int,
                  rnd: int) -> bool:
    """Change-only ``gossip.frontier`` event + board state + the
    ``cluster.frontier`` fingerprint gauge.  Returns True when the
    frontier actually moved (callers use this to notice out-of-band
    content changes, e.g. the sim's fan-out leg)."""
    if PROPAGATION.note_frontier(replica, digest_hex, records, rnd):
        _emit("gossip.frontier", replica=replica, round=int(rnd),
              digest=digest_hex, records=int(records))
        return True
    return False


# re-exported so instrumentation call sites can assert the plane's own
# gate state in tests without importing metrics twice
OBS = _OBS
