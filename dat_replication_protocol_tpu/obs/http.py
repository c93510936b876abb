"""Sidecar scrape endpoint: pull-based fleet telemetry over stdlib HTTP.

The fleet plane's transport layer (ISSUE 11).  One daemon thread runs a
``http.server.ThreadingHTTPServer`` serving four strictly READ-ONLY
routes off the same locked snapshots ``--stats-fd`` uses — a scraping
client can never perturb the hot path, because nothing here mutates
session state, takes a device dispatch, or holds a session lock while
rendering (the overhead-budget test in tests/test_obs_fleet.py proves
the budget; the datlint healthz check proves the lock discipline for
the liveness route):

* ``GET /metrics``  — Prometheus text exposition
  (:func:`~.metrics.to_prom_text` over the live registry, labeled
  collector entries included);
* ``GET /snapshot`` — the full JSON stats record (registry snapshot +
  ``jit_sites`` + ``watermarks`` + hub/fanout breakdowns when the
  caller's ``snapshot_fn`` carries them — the sidecar passes its
  ``snapshot_stats``, so the endpoint and ``--stats-fd`` serve the
  SAME dict);
* ``GET /healthz``  — staged health: admission open/closed (a
  LOCK-FREE callable the owner installs — see
  ``ReplicationHub.admission_state``), event-loop lag, whether
  the flight recorder is armed and the obs gate is on.  HTTP 200 when
  every stage is healthy, 503 otherwise — load-balancer compatible.
  The handler must never take a device or hub lock: a wedged engine
  must not wedge the probe that exists to detect it (enforced by the
  datlint obs-discipline healthz check);
* ``GET /events``   — bounded JSONL tail of the structured event ring
  (``?n=`` caps the tail, default 256).

Zero dependencies, pull-based, no coordination: replicas export, an
aggregator (:mod:`.fleet`) joins — "Simplicity Scales".
"""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional
from urllib.parse import parse_qs, urlparse

from . import device as _device
from .events import EVENTS as _EVENTS
from .flight import FLIGHT as _FLIGHT
from .metrics import OBS as _OBS, REGISTRY as _REGISTRY, to_prom_text
from .watermarks import WATERMARKS as _WATERMARKS

__all__ = ["ObsHttpServer", "default_snapshot", "default_healthz",
           "DEFAULT_EVENTS_TAIL"]

DEFAULT_EVENTS_TAIL = 256
_MAX_EVENTS_TAIL = 4096


def default_snapshot() -> dict:
    """The core stats record for processes that are not the sidecar
    (embedded fleets, tests): registry + device sentinel +
    watermarks + ring health.  The sidecar passes its richer
    ``snapshot_stats`` (same shape plus hub/fanout breakdowns)."""
    return {
        "ts": time.time(),
        "monotonic": time.monotonic(),
        "metrics": _REGISTRY.snapshot(),
        "events_dropped": _EVENTS.dropped,
        "jit_sites": _device.SENTINEL.snapshot(),
        "watermarks": _WATERMARKS.snapshot(),
    }


def default_healthz(admission_fn: Optional[Callable[[], dict]] = None
                    ) -> dict:
    """Staged health record (ROBUSTNESS.md: the stages mirror the
    staged-overload contract — each one names the FIRST line of defense
    that is currently degraded, not a single opaque boolean).

    Lock discipline: everything read here is either a plain attribute
    (``OBS.on``, ``FLIGHT.armed``) or ``admission_fn`` — which owners
    must implement lock-free (``ReplicationHub.admission_state`` is
    the reference).  The datlint obs-discipline healthz check enforces
    the no-device/hub-lock half mechanically on this module."""
    stages: dict = {}
    ok = True
    # stage 1: admission (hub/fanout owners install the callable)
    if admission_fn is not None:
        try:
            # the admission_state contract (datlint healthz check):
            # lock-free attribute reads only — a health probe must
            # never block behind an engine lock
            # datlint: allow-callback-escape
            adm = admission_fn()
        except Exception as e:
            adm = {"open": False, "error": f"{type(e).__name__}: {e}"}
        stages["admission"] = {"ok": bool(adm.get("open")), **adm}
        ok = ok and bool(adm.get("open"))
    # stage 2: event-loop lag (ISSUE 18) — a loop that has fallen
    # behind its tick is degraded the same way a closed admission gate
    # is: the flight deck's live lag view, plain attribute reads off
    # each loop's profiler (lock-free, at worst one turn stale).  Dark
    # loops (gate off) report state only — a stale zero must not read
    # as healthy OR degraded
    loops = _WATERMARKS.loops_now()
    if loops:
        behind = sorted(name for name, rec in loops.items()
                        if rec.get("state") == "live"
                        and rec.get("behind"))
        lag = {name: rec.get("lag_s", 0.0) for name, rec in
               loops.items() if rec.get("state") == "live"}
        stages["loop_lag"] = {"ok": not behind, "behind": behind,
                              "lag_s": lag}
        ok = ok and not behind
    # stage 3: observability itself (armed recorder, live gate)
    stages["flight_recorder"] = {"ok": True, "armed": _FLIGHT.armed}
    stages["obs_gate"] = {"ok": True, "on": _OBS.on}
    return {"ok": ok, "stages": stages, "ts": time.time(),
            "monotonic": time.monotonic()}


class _Handler(BaseHTTPRequestHandler):
    # set per-server via the factory in ObsHttpServer
    server_version = "dat-obs/1"
    protocol_version = "HTTP/1.1"
    # bounded per-connection reads (the bounded-wait doctrine): a
    # half-open scraper that connects and never sends a request line —
    # or parks an idle keep-alive — must release its handler thread
    # instead of pinning one forever
    timeout = 30.0

    def log_message(self, fmt, *args):  # stderr chatter off the hot path
        pass

    def _send(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        try:
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass  # a vanished scraper is its own problem

    def do_GET(self) -> None:  # noqa: N802 — BaseHTTPRequestHandler API
        try:
            url = urlparse(self.path)
            route = url.path.rstrip("/") or "/"
            if route == "/metrics":
                body = to_prom_text().encode("utf-8")
                self._send(200, body, "text/plain; version=0.0.4")
            elif route == "/snapshot":
                snap = self.server.obs_snapshot_fn()  # type: ignore[attr-defined]
                body = (json.dumps(snap, default=repr) + "\n").encode()
                self._send(200, body, "application/json")
            elif route == "/healthz":
                hz = self._healthz()
                body = (json.dumps(hz, default=repr) + "\n").encode()
                self._send(200 if hz.get("ok") else 503, body,
                           "application/json")
            elif route == "/events":
                n = DEFAULT_EVENTS_TAIL
                q = parse_qs(url.query)
                if "n" in q:
                    try:
                        n = max(1, min(_MAX_EVENTS_TAIL, int(q["n"][0])))
                    except ValueError:
                        pass
                tail = _EVENTS.events()[-n:]
                body = "".join(
                    json.dumps(r, default=repr) + "\n" for r in tail
                ).encode("utf-8")
                self._send(200, body, "application/x-ndjson")
            else:
                self._send(404, b'{"error": "unknown route"}\n',
                           "application/json")
        except Exception as e:  # a broken route must not kill the thread
            try:
                self._send(500, (json.dumps(
                    {"error": f"{type(e).__name__}: {e}"}) + "\n").encode(),
                    "application/json")
            except Exception:
                pass

    def _healthz(self) -> dict:
        """The liveness route.  READ-ONLY, lock-discipline-checked:
        nothing in this method (or the default it delegates to) may
        take a device or hub lock — see module docstring."""
        fn = self.server.obs_healthz_fn  # type: ignore[attr-defined]
        return fn()


class ObsHttpServer:
    """The ``--obs-http`` endpoint: bind, serve on a daemon thread,
    close.  ``port=0`` binds an ephemeral port (tests); the bound port
    is ``self.port`` after construction."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1", *,
                 snapshot_fn: Optional[Callable[[], dict]] = None,
                 healthz_fn: Optional[Callable[[], dict]] = None,
                 admission_fn: Optional[Callable[[], dict]] = None):
        if healthz_fn is None:
            healthz_fn = lambda: default_healthz(admission_fn)  # noqa: E731
        self._srv = ThreadingHTTPServer((host, port), _Handler)
        self._srv.daemon_threads = True
        # handler plumbing rides the server object (stdlib idiom: the
        # handler sees it as self.server)
        self._srv.obs_snapshot_fn = snapshot_fn or default_snapshot
        self._srv.obs_healthz_fn = healthz_fn
        self.host, self.port = self._srv.server_address[:2]
        self._thread: Optional[threading.Thread] = None
        self._closed = False

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> "ObsHttpServer":
        self._thread = threading.Thread(
            target=self._srv.serve_forever, name="obs-http", daemon=True,
            kwargs={"poll_interval": 0.1})
        self._thread.start()
        return self

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._srv.shutdown()
        self._srv.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def __enter__(self) -> "ObsHttpServer":
        return self.start() if self._thread is None else self

    def __exit__(self, *exc) -> None:
        self.close()
