#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served digest path still
starts, and is right, on the chip.

    python chip_smoke.py [--seed N] [--only STAGE[,STAGE...]] [--dry-run]

One process per chip: this parent never imports jax.  Each stage is one
child process that owns the device, run one after another:

  plain   `python -m dat_replication_protocol_tpu.sidecar --tcp ...`
          serving one publisher-shaped client session over TCP: 1 MiB
          blobs back to back (two batches at the 1,024-item / 1 GiB
          cap), then small changes, then two blobs over the stream
          threshold (hashed on the host by design — reported).
  hub     the same traffic split over 8 concurrent sessions against
          `--edge --hub`.
  second  the plain sidecar started again and sent a shorter session of
          whole batches, so every shape repeats: it writes no cache
          entry, and hits whatever the first start wrote.
  ops     one child driving the public ops at BASELINE.json sizes:
          `runtime.content_address` (device route vs native host route
          vs hashlib), the `ops.merkle` diff of two 1M-leaf snapshots,
          `ops.rateless.CodedSymbols(engine="device")` vs `"host"`, and
          every Pallas kernel checked to lower to a Mosaic custom call.
  mesh    only with more than one device visible: a `--hub-mesh auto`
          session and `dryrun_multichip(n)` with shards on distinct
          devices.

Every digest and result is compared with a plain reference computed
here (hashlib, a numpy compare, the host engines).  All data comes from
`--seed`.  The script exits non-zero, naming the stage, if no TPU is
present (before serving a byte), if a stage raises, if any result
differs from its reference, or if a stage's own counters say the work
did not run on the TPU.  On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

`--dry-run` is for debugging the script itself on a CPU host: tiny
sizes, host engines allowed, device-only checks skipped, and a last
line that says `"dry_run": true` and carries no `"ok"`.

Cuts of scale against the sources (widths are never cut) are listed in
REDUCED and printed with the report.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

STAGES = ("plain", "hub", "second", "ops", "mesh")

FULL = {
    "blobs": 2048, "blob_bytes": 1 << 20,        # BASELINE config 3 width
    "changes": 200_000, "value_min": 100, "value_max": 1000,
    "big_blobs": 2, "big_bytes": 64 << 20,       # over the stream threshold
    "hub_sessions": 8,
    "second_blobs": 1024, "second_changes": 20_480,
    "mesh_blobs": 256, "mesh_changes": 20_480,
    "cdc_bytes": 1 << 30, "route_bytes": 64 << 20,
    "merkle_leaves": 1 << 20, "merkle_changed": 1000,
    "rateless_digests": 1_000_000, "rateless_symbols": (4096, 16384),
}
DRY = {
    "blobs": 48, "blob_bytes": 4096,
    "changes": 2500, "value_min": 100, "value_max": 1000,
    "big_blobs": 2, "big_bytes": 9 << 20,
    "hub_sessions": 4,
    "second_blobs": 24, "second_changes": 1024,
    "mesh_blobs": 8, "mesh_changes": 256,
    "cdc_bytes": 1 << 18, "route_bytes": 1 << 18,
    "merkle_leaves": 1 << 10, "merkle_changed": 10,
    "rateless_digests": 2000, "rateless_symbols": (64, 256),
}
REDUCED = [
    "served legs: 2,048 blobs of 1 MiB, a fifth of BASELINE config 3's "
    "10,240 (the blob width and the 1,024-item / 1 GiB batch cap are the "
    "source's)",
    "ops: content_address over 1 GiB, a tenth of BASELINE config 4's "
    "10 GiB (one residency; tile and chunk widths are the source's)",
    "ops: the first/fused/fused1p CDC routes are checked over 64 MiB, "
    "the default route over the full 1 GiB",
    "mesh leg (more than one device only): 256 blobs and 20,480 changes",
]

# the batch cap the served path is held to (backend.tpu_backend.
# DigestPipeline defaults): 1,024 items or 1 GiB, whichever first
BATCH_ITEMS = 1024
HUB_PARKED_BUDGET = 1 << 30

PALLAS_KERNELS = (
    "blake2b_native", "merkle_level_native", "gear_candidates_native",
    "gear_first_native", "gear_window_first_native", "fused_cdc_hash",
)


class SmokeFailure(Exception):
    def __init__(self, stage: str, reason: str):
        super().__init__(f"stage={stage}: {reason}")
        self.stage = stage


def say(*a) -> None:
    print(*a, flush=True)


def need(stage: str, cond, reason: str) -> None:
    if not cond:
        raise SmokeFailure(stage, reason)


# ---------------------------------------------------------------------------
# the sidecar child
# ---------------------------------------------------------------------------


class Sidecar:
    """One `sidecar --tcp` child with its stats pipe tailed (a supervisor
    that stops reading makes the emitter tear a record and latch dead)
    and its stderr scanned for the port and the obs endpoint."""

    def __init__(self, stage: str, flags: list[str]):
        self.stage = stage
        r, w = os.pipe()
        os.set_inheritable(w, True)
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dat_replication_protocol_tpu.sidecar",
             "--tcp", "127.0.0.1:0", "--stats-fd", str(w),
             "--stats-interval", "1", "--obs-http", "0", *flags],
            cwd=REPO, pass_fds=(w,), close_fds=True,
            stdin=subprocess.DEVNULL, stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True)
        os.close(w)
        self._stats_r = r
        self.port = None
        self.obs_url = None
        self.device_line = None
        self.t_listening = None
        self.stderr_tail: list[str] = []
        self.snapshots: list[dict] = []
        self._cv = threading.Condition()
        self._threads = [
            threading.Thread(target=self._tail_stderr, daemon=True),
            threading.Thread(target=self._tail_stats, daemon=True),
        ]
        for t in self._threads:
            t.start()

    def _tail_stderr(self) -> None:
        for line in self.proc.stderr:
            line = line.rstrip("\n")
            with self._cv:
                self.stderr_tail.append(line)
                del self.stderr_tail[:-40]
                m = re.search(r"listening on \S*:(\d+)$", line)
                if m and "obs" not in line:
                    self.port = int(m.group(1))
                    self.t_listening = time.monotonic()
                m = re.search(r"obs endpoint on (\S+)", line)
                if m:
                    self.obs_url = m.group(1)
                if line.startswith("sidecar: device "):
                    self.device_line = line
                self._cv.notify_all()

    def _tail_stats(self) -> None:
        buf = b""
        while True:
            chunk = os.read(self._stats_r, 1 << 16)
            if not chunk:
                return
            buf += chunk
            *lines, buf = buf.split(b"\n")
            with self._cv:
                for ln in lines:
                    if ln.strip():
                        self.snapshots.append(json.loads(ln))
                self._cv.notify_all()

    def _wait(self, pred, timeout: float, what: str):
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                got = pred()
                if got:
                    return got
                if self.proc.poll() is not None:
                    raise SmokeFailure(
                        self.stage,
                        f"sidecar exited {self.proc.returncode} before "
                        f"{what}:\n" + "\n".join(self.stderr_tail[-12:]))
                left = deadline - time.monotonic()
                if left <= 0:
                    raise SmokeFailure(
                        self.stage, f"timed out after {timeout:.0f}s "
                        f"waiting for {what}")
                self._cv.wait(min(left, 0.5))

    def wait_ready(self, timeout: float = 300.0) -> dict:
        """Block until the sidecar listens; return its `device` record
        (from a kicked stats snapshot, the same record every line
        carries)."""
        self._wait(lambda: self.port and self.obs_url, timeout,
                   "the listening line")
        self.ready_snapshot = self.kick(lambda s: True, 60.0)
        return self.ready_snapshot["device"]

    def kick(self, pred, timeout: float) -> dict:
        """SIGUSR1 a fresh snapshot until one satisfies `pred`."""
        seen = len(self.snapshots)

        def fresh():
            return next((s for s in self.snapshots[seen:] if pred(s)), None)

        deadline = time.monotonic() + timeout
        while True:
            self.proc.send_signal(signal.SIGUSR1)
            try:
                return self._wait(fresh, 2.0, "a stats snapshot")
            except SmokeFailure:
                if self.proc.poll() is not None \
                        or time.monotonic() > deadline:
                    raise

    def events(self) -> list[dict]:
        with urllib.request.urlopen(self.obs_url + "/events?n=1024",
                                    timeout=30) as resp:
            body = resp.read().decode()
        return [json.loads(ln) for ln in body.splitlines() if ln.strip()]

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=15)
        for t in self._threads:
            t.join(timeout=5)
        os.close(self._stats_r)


# ---------------------------------------------------------------------------
# the client: a publisher's session, digests checked against hashlib
# ---------------------------------------------------------------------------


def _h(data) -> bytes:
    return hashlib.blake2b(data, digest_size=32).digest()


class Session:
    """One client session: frames go out on the calling thread while a
    reader thread decodes the digest reply and compares every digest
    with the one hashlib gave for the same bytes."""

    def __init__(self, stage: str, port: int, name: str):
        import dat_replication_protocol_tpu as protocol

        self.stage = stage
        self.name = name
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        self.sock.settimeout(900)
        self.expected = {"blob": [], "change": []}
        self.got = {"blob": 0, "change": 0}
        self.bad = 0
        self.first_bad = None
        self.finalized_after = None
        self.error = None
        self.sent_bytes = 0
        self.t0 = self.t_done = None
        dec = protocol.decode()
        dec.change(self._on_reply)
        dec.finalize(self._on_finalize)
        dec.on_error(lambda e: setattr(self, "error", e))
        self._dec = dec
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _on_reply(self, c, done) -> None:
        kind, _, seq = c.key.partition("-")
        try:
            want = self.expected[kind][int(seq)]
        except (KeyError, ValueError, IndexError):
            self._bad(f"unexpected reply key {c.key!r}")
        else:
            if c.value != want or c.subset != f"digest:{kind}" \
                    or c.change != int(seq):
                self._bad(f"{c.key}: digest differs from hashlib")
            self.got[kind] += 1
        done()

    def _bad(self, what: str) -> None:
        self.bad += 1
        self.first_bad = self.first_bad or what

    def _on_finalize(self, done) -> None:
        self.finalized_after = self.got["blob"] + self.got["change"]
        done()

    def _read(self) -> None:
        try:
            while True:
                data = self.sock.recv(1 << 20)
                if not data:
                    break
                self._dec.write(data)
            self._dec.end()
        except OSError as e:
            self.error = e
        self.t_done = time.monotonic()

    def send(self, data) -> None:
        if self.t0 is None:
            self.t0 = time.monotonic()
        self.sock.sendall(data)
        self.sent_bytes += len(data)

    def blob(self, data: bytes) -> None:
        from dat_replication_protocol_tpu.wire import TYPE_BLOB, frame_header

        self.expected["blob"].append(_h(data))
        self.send(frame_header(len(data), TYPE_BLOB))
        self.send(data)

    def changes(self, payloads: list[bytes]) -> None:
        from dat_replication_protocol_tpu.wire import TYPE_CHANGE, frame

        exp = self.expected["change"]
        out, size = [], 0
        for p in payloads:
            exp.append(_h(p))
            fr = frame(TYPE_CHANGE, p)
            out.append(fr)
            size += len(fr)
            if size >= 1 << 18:
                self.send(b"".join(out))
                out, size = [], 0
        if out:
            self.send(b"".join(out))

    def finish(self, timeout: float = 900.0) -> dict:
        self.sock.shutdown(socket.SHUT_WR)
        self._reader.join(timeout)
        st = self.stage
        need(st, not self._reader.is_alive(),
             f"{self.name}: no reply EOF within {timeout:.0f}s")
        self.sock.close()
        need(st, self.error is None, f"{self.name}: {self.error!r}")
        need(st, not self.bad,
             f"{self.name}: {self.bad} bad replies, first: {self.first_bad}")
        for kind in ("blob", "change"):
            need(st, self.got[kind] == len(self.expected[kind]),
                 f"{self.name}: {self.got[kind]} {kind} digests for "
                 f"{len(self.expected[kind])} sent")
        total = self.got["blob"] + self.got["change"]
        need(st, self._dec.finished and self.finalized_after == total,
             f"{self.name}: reply finalised after "
             f"{self.finalized_after} of {total} digests")
        return {"blobs": self.got["blob"], "changes": self.got["change"],
                "sent_bytes": self.sent_bytes,
                "session_s": round(self.t_done - self.t0, 3)}


CHANGE_STEP = 8192


def change_chunks(seed: int, stream: int, count: int, sizes: dict):
    """The first `count` records of the (seed, stream) change feed, in
    chunks of encoded Change payloads (values 100-1,000 B).  Each chunk
    has its own generator and is always drawn whole, so a shorter
    session is an exact prefix of a longer one — which is what lets the
    second start repeat the first's shapes."""
    import numpy as np

    from dat_replication_protocol_tpu import encode_change

    for at in range(0, count, CHANGE_STEP):
        rng = np.random.default_rng([seed, 1, stream, at])
        lens = rng.integers(sizes["value_min"], sizes["value_max"] + 1,
                            size=CHANGE_STEP)
        raw = rng.bytes(int(lens.sum()))
        offs = np.concatenate([[0], np.cumsum(lens)])
        yield [encode_change({
            "key": f"k{stream}-{i:08d}", "change": i, "from": i,
            "to": i + 1, "value": raw[offs[i - at]:offs[i - at + 1]]})
            for i in range(at, min(at + CHANGE_STEP, count))]


def publish(sess: Session, seed: int, stream: int, blobs: int,
            changes: int, big: int, sizes: dict) -> dict:
    """A publisher's shape: the blob run back to back (interleaved with
    changes it would never fill a batch: the per-session pipeline cuts
    at 1,024 items of any kind), then the changes, then the big blobs."""
    import numpy as np

    rng = np.random.default_rng([seed, 0, stream])
    for _ in range(blobs):
        sess.blob(rng.bytes(sizes["blob_bytes"]))
    for chunk in change_chunks(seed, stream, changes, sizes):
        sess.changes(chunk)
    big_rng = np.random.default_rng([seed, 2, stream])
    for _ in range(big):
        sess.blob(big_rng.bytes(sizes["big_bytes"]))
    return sess.finish()


# ---------------------------------------------------------------------------
# served stages
# ---------------------------------------------------------------------------


def served_report(sc: Sidecar, device: dict, snap: dict,
                  sessions: list[dict]) -> dict:
    m = snap["metrics"]
    c, g = m["counters"], m["gauges"]
    # compile seconds spent before the sidecar listened are already in
    # startup_s: count only the session's own
    g0 = sc.ready_snapshot["metrics"]["gauges"]
    trace_s = g.get("device.compile.trace_seconds", 0.0) \
        - g0.get("device.compile.trace_seconds", 0.0)
    backend_s = g.get("device.compile.backend_seconds", 0.0) \
        - g0.get("device.compile.backend_seconds", 0.0)
    session_s = max(s["session_s"] for s in sessions)
    startup_s = sc.t_listening - sc.t_spawn
    selects = [e["fields"] for e in sc.events()
               if e.get("event") == "device.engine.select"
               and e["fields"].get("component") == "blake2b.batch"]
    return {
        "device": device,
        "device_line": sc.device_line,
        "startup_s": round(startup_s, 2),
        "session_s": round(session_s, 2),
        "compile_trace_s": round(trace_s, 2),
        "compile_backend_s": round(backend_s, 2),
        # set-up is what a warm second start saves; run is the rest
        "setup_s": round(startup_s + trace_s + backend_s, 2),
        "run_s": round(max(0.0, session_s - trace_s - backend_s), 2),
        "programs_traced": c.get("device.jit.traces", 0),
        "jit_sites": snap["jit_sites"],
        "cache": {k: c.get(f"device.compile.cache.{k}", 0)
                  for k in ("requests", "hits", "misses")},
        "blake2b_buckets": snap["blake2b_buckets"],
        "bucket_engine_selects": [
            {k: f.get(k) for k in ("nblocks", "engine", "items")}
            for f in selects],
        "h2d_bytes": c.get("device.h2d.bytes", 0),
        "d2h_bytes": c.get("device.d2h.bytes", 0),
        "host_stream_bytes": c.get("device.host.stream.bytes", 0),
        "host_engine_bytes": c.get("device.native.hash.bytes", 0),
        "submit_items": c.get("device.submit.items", 0),
        "dispatch_batches": c.get("device.dispatch.batches", 0),
        "peak_bytes_in_use": g.get("device.mem.peak_bytes_in_use"),
        "sessions": sessions,
    }


def check_on_device(stage: str, rep: dict, items: int, big_bytes: int,
                    dry: bool) -> None:
    """A stage's own counters must say the work ran on the TPU."""
    need(stage, rep["host_stream_bytes"] == big_bytes,
         f"{rep['host_stream_bytes']} stream bytes hashed on the host, "
         f"expected the {big_bytes} over the stream threshold")
    if dry:
        return
    d = rep["device"]
    need(stage, d["platform"] == "tpu"
         and d["engine"] in ("device-batch", "device-batch-mesh"),
         f"sidecar resolved {d}")
    need(stage, rep["h2d_bytes"] > 0 and rep["d2h_bytes"] > 0,
         "no H2D/D2H bytes counted")
    need(stage, rep["host_engine_bytes"] == 0,
         f"{rep['host_engine_bytes']} bytes went through the host engine")
    # both engines end in the one bucket table (since ISSUE 35 the mesh
    # engine is the served one laid over the chips)
    in_buckets = sum(r["items"] for r in rep["blake2b_buckets"].values())
    need(stage, in_buckets == items,
         f"{in_buckets} items in device buckets, {items} batched items "
         f"sent")
    if d["engine"] == "device-batch-mesh":
        # the mesh arm once hashed with the XLA scan on every chip and
        # noted no bucket at all; that must not come back unseen
        engines = {k.split(":")[0] for k in rep["blake2b_buckets"]}
        need(stage, engines == {"pallas"},
             f"engines that served the mesh hub's buckets: "
             f"{sorted(engines)} ({rep['blake2b_buckets']})")
        n = d["mesh_devices"]
        odd = {k: r for k, r in rep["blake2b_buckets"].items()
               if r["padded_items"] % (n * 32)}
        need(stage, not odd,
             f"bucket rows not a whole tile on each of {n} chips: {odd}")


def refuse_without_tpu(stage: str, device: dict, dry: bool) -> None:
    if not dry:
        need(stage, device.get("platform") == "tpu",
             f"no accelerator: the sidecar resolved {device}; nothing "
             f"was served")


def stage_plain(seed: int, sizes: dict, dry: bool, second: bool = False,
                first: dict | None = None) -> dict:
    stage = "second" if second else "plain"
    blobs = sizes["second_blobs" if second else "blobs"]
    changes = sizes["second_changes" if second else "changes"]
    big = 0 if second else sizes["big_blobs"]
    sc = Sidecar(stage, [])
    try:
        device = sc.wait_ready()
        refuse_without_tpu(stage, device, dry)
        res = publish(Session(stage, sc.port, "c1"), seed, 0, blobs,
                      changes, big, sizes)
        snap = sc.kick(lambda s: s["metrics"]["counters"].get(
            "sidecar.sessions", 0) >= 1, 60.0)
        rep = served_report(sc, device, snap, [res])
    finally:
        sc.stop()
    check_on_device(stage, rep, blobs + changes,
                    big * sizes["big_bytes"], dry)
    if not dry:
        nb = -(-sizes["blob_bytes"] // 128)
        row = rep["blake2b_buckets"].get(f"pallas:{nb}", {})
        full = blobs // BATCH_ITEMS
        need(stage, row.get("items") == blobs
             and row.get("dispatches") == full,
             f"blob run did not fill {full} batches at the "
             f"{BATCH_ITEMS}-item / 1 GiB cap on the pallas engine: "
             f"bucket pallas:{nb} = {row}")
        engines = {k.split(":")[0] for k in rep["blake2b_buckets"]}
        # on a chip every bucket, the small change buckets included,
        # runs the Pallas program of its slot width: the scan is the
        # CPU's engine
        need(stage, engines == {"pallas"},
             f"engines that served buckets: {sorted(engines)}")
    if second and not dry:
        # a repeated session writes no entry, and hits what the first
        # start wrote — if it wrote any: the served programs are a few
        # Pallas programs that may each compile under jax's floor for
        # the persistent cache, and then a second start has nothing to
        # read and nothing to save
        wrote = first is not None and first["cache"]["misses"] > 0
        need(stage, rep["cache"]["misses"] == 0
             and (rep["cache"]["hits"] > 0 or not wrote),
             f"persistent cache on a repeated session: {rep['cache']}"
             f" (the first start's: {first and first['cache']})")
        if wrote:
            need(stage, rep["setup_s"] < first["setup_s"],
                 f"second start set-up {rep['setup_s']}s, first "
                 f"{first['setup_s']}s")
    return rep


def stage_hub(seed: int, sizes: dict, dry: bool, mesh: bool = False) -> dict:
    stage = "mesh" if mesh else "hub"
    n = sizes["hub_sessions"]
    blobs = sizes["mesh_blobs" if mesh else "blobs"]
    changes = sizes["mesh_changes" if mesh else "changes"]
    big = 0 if mesh else sizes["big_blobs"]
    flags = ["--hub", "--hub-parked-budget", str(HUB_PARKED_BUDGET)]
    flags += ["--hub-mesh", "auto"] if mesh else ["--edge"]
    sc = Sidecar(stage, flags)
    try:
        device = sc.wait_ready()
        refuse_without_tpu(stage, device, dry)
        if mesh and not dry:
            need(stage, device["engine"] == "device-batch-mesh"
                 and device["mesh_devices"] > 1, f"sidecar resolved {device}")
        # connect every session first: the hub refuses NEW sessions once
        # half its parked budget is in use
        sessions = [Session(stage, sc.port, f"c{i + 1}") for i in range(n)]
        results: list = [None] * n

        def run(i: int) -> None:
            try:
                results[i] = publish(
                    sessions[i], seed, i, blobs // n, changes // n,
                    1 if i < big else 0, sizes)
            except Exception as e:  # noqa: BLE001 — reported below
                results[i] = e

        threads = [threading.Thread(target=run, args=(i,), daemon=True)
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(1200)
        for i, r in enumerate(results):
            if isinstance(r, SmokeFailure):
                raise r
            need(stage, isinstance(r, dict), f"session c{i + 1}: {r!r}")
        snap = sc.kick(
            lambda s: (s.get("edge", {}).get("served", 0) >= n if not mesh
                       else s["metrics"]["counters"].get(
                           "sidecar.sessions", 0) >= n), 60.0)
        rep = served_report(sc, device, snap, results)
        c = snap["metrics"]["counters"]
        batches = c.get("hub.dispatch.batches", 0)
        rep["hub"] = {
            **{k: c.get(f"hub.{k}", 0)
               for k in ("admitted", "rejected", "shed")},
            "failed": snap["hub"]["failed"],
            "pump_route": snap["hub"]["pump_route"],
            "parked_budget": HUB_PARKED_BUDGET,
            "dispatch_batches": batches,
            "dispatch_items": c.get("hub.dispatch.items", 0),
            # an observation for the next issue, not a claim: what the
            # hub's linger composes (its row count is declared per slot
            # width: ops/blake2b.batch_rows)
            "items_per_batch": round(
                c.get("hub.dispatch.items", 0) / max(1, batches), 1),
        }
    finally:
        sc.stop()
    need(stage, rep["hub"]["failed"] is None
         and rep["hub"]["admitted"] == n
         and rep["hub"]["rejected"] == rep["hub"]["shed"] == 0,
         f"hub admission/shed counters: {rep['hub']}")
    check_on_device(stage, rep, (blobs // n + changes // n) * n,
                    big * sizes["big_bytes"], dry)
    return rep


# ---------------------------------------------------------------------------
# children that drive the ops in-process (they own the chip)
# ---------------------------------------------------------------------------


def _child_prologue(dry: bool) -> dict:
    from dat_replication_protocol_tpu.obs import device as obs_device
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics
    from dat_replication_protocol_tpu.utils.cache import enable_compile_cache
    from dat_replication_protocol_tpu.utils.routing import describe_device

    enable_compile_cache()
    obs_metrics.enable()
    obs_device.watch_compile_events()
    device = describe_device()
    if device["platform"] != "tpu" and not dry:
        raise SystemExit(f"no accelerator: jax initialised {device}")
    return device


def _child_epilogue(out: dict, t0: float) -> None:
    import jax

    from dat_replication_protocol_tpu.obs import device as obs_device
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    obs_device.sample_device_gauges()
    m = obs_metrics.snapshot()
    c, g = m["counters"], m["gauges"]
    out.update({
        "jax": jax.__version__,
        "wall_s": round(time.monotonic() - t0, 2),
        # jax times a nested jit's trace once per enclosing trace too,
        # so with nested kernels this can exceed the wall clock; each
        # op above reports first (cold) against warm seconds instead
        "compile_trace_s": round(
            g.get("device.compile.trace_seconds", 0.0), 2),
        "compile_backend_s": round(
            g.get("device.compile.backend_seconds", 0.0), 2),
        "programs_traced": c.get("device.jit.traces", 0),
        "jit_sites": obs_device.SENTINEL.snapshot(),
        "cache": {k: c.get(f"device.compile.cache.{k}", 0)
                  for k in ("requests", "hits", "misses")},
        "blake2b_buckets": obs_device.BUCKETS.snapshot(),
        "h2d_bytes": c.get("device.h2d.bytes", 0),
        "d2h_bytes": c.get("device.d2h.bytes", 0),
        "host_engine_bytes": c.get("device.native.hash.bytes", 0),
        "peak_bytes_in_use": g.get("device.mem.peak_bytes_in_use"),
    })
    print(json.dumps(out), flush=True)


@contextlib.contextmanager
def _env(**kv):
    """Set routing overrides for one block (the documented way to pin an
    engine: DAT_DEVICE_CDC / DAT_CDC_ROUTE are read per call)."""
    old = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _timed(fn):
    """(result, seconds): every op here ends in a host fetch, so the
    result is complete when it returns."""
    t = time.monotonic()
    out = fn()
    return out, round(time.monotonic() - t, 2)


def _mosaic_kernels() -> dict:
    """Lower every Pallas kernel at its smallest legal shape and check
    the module carries a Mosaic custom call (an interpreted kernel
    lowers to plain HLO instead)."""
    import jax.numpy as jnp

    from dat_replication_protocol_tpu.ops import (
        blake2b_pallas, fused_cdc_hash_pallas, merkle_pallas, rabin,
        rabin_pallas)

    u32 = jnp.uint32
    words = jnp.zeros((2, rabin.GROUP // 4, 8, 1024), u32)
    lowered = {
        "blake2b_native": blake2b_pallas.blake2b_native.lower(
            jnp.zeros((1, 16, 8, 128), u32), jnp.zeros((1, 16, 8, 128), u32),
            jnp.zeros((8, 128), u32)),
        "merkle_level_native": merkle_pallas.merkle_level_native.lower(
            jnp.zeros((8, 8, 128), u32), jnp.zeros((8, 8, 128), u32)),
        "gear_candidates_native": rabin_pallas.gear_candidates_native.lower(
            words),
        "gear_first_native": rabin_pallas.gear_first_native.lower(words),
        "gear_window_first_native":
            rabin_pallas.gear_window_first_native.lower(words, 13, 8),
        "fused_cdc_hash":
            fused_cdc_hash_pallas.gear_window_first_checked_native.lower(
                words, 13, 8),
    }
    return {name: "tpu_custom_call" in low.as_text()
            for name, low in lowered.items()}


def child_ops(seed: int, sizes: dict, dry: bool) -> None:
    t0 = time.monotonic()
    device = _child_prologue(dry)
    import numpy as np

    from dat_replication_protocol_tpu import runtime
    from dat_replication_protocol_tpu.ops import merkle, rabin
    from dat_replication_protocol_tpu.ops.rateless import CodedSymbols

    out: dict = {"device": device}
    rng = np.random.default_rng([seed, 3])
    force = {"DAT_DEVICE_CDC": "1", "DAT_DEVICE_MERKLE": "1"} if dry else {}

    # content addressing: device route vs native host route vs hashlib
    buf = np.frombuffer(rng.bytes(sizes["cdc_bytes"]), np.uint8)
    with _env(**force):
        dev, first_s = _timed(lambda: runtime.content_address(buf))
        warm, warm_s = _timed(lambda: runtime.content_address(buf))
    assert warm == dev, "a second content_address differs from the first"
    out["content_address_s"] = {"first": first_s, "warm": warm_s}
    with _env(DAT_DEVICE_CDC="0"):
        host = runtime.content_address(buf)
    assert dev.cuts == host.cuts, "device cuts differ from the host route"
    assert np.array_equal(dev.digests, host.digests), \
        "device chunk digests differ from the host route"
    assert dev.root == host.root, "device root differs from the host route"
    offs, lens = dev.extents()
    raw = buf.tobytes()
    for i in range(dev.nchunks):
        o, ln = int(offs[i]), int(lens[i])
        assert dev.digests[i].tobytes() == _h(raw[o:o + ln]), \
            f"chunk {i} digest differs from hashlib"
    out["content_address"] = {"bytes": int(buf.size), "chunks": dev.nchunks,
                              "route": rabin.effective_route()}
    # the other extraction kernels, over a prefix
    sub = buf[: sizes["route_bytes"]]
    with _env(DAT_DEVICE_CDC="0"):
        want = rabin.chunk_stream(sub)
    routes = {}
    for route in ("bitmask", "first", "fused", "fused1p"):
        with _env(DAT_CDC_ROUTE=route, **force):
            routes[route] = rabin.effective_route()
            assert rabin.chunk_stream(sub) == want, \
                f"route {route} cuts differ from the host route"
    out["cdc_routes"] = routes

    # merkle diff of two snapshots vs a plain compare of the leaves
    n, k = sizes["merkle_leaves"], sizes["merkle_changed"]
    a = rng.integers(0, 256, (n, 32), dtype=np.uint8)
    b = a.copy()
    changed = np.sort(rng.choice(n, k, replace=False))
    b[changed, 0] ^= 1
    a_hh, a_hl = merkle.digests_to_device([r.tobytes() for r in a])
    b_hh, b_hl = merkle.digests_to_device([r.tobytes() for r in b])
    with _env(**force):
        got, first_s = _timed(
            lambda: merkle.diff_snapshots(a_hh, a_hl, b_hh, b_hl))
        _, warm_s = _timed(
            lambda: merkle.diff_snapshots(a_hh, a_hl, b_hh, b_hl))
    out["merkle_diff_s"] = {"first": first_s, "warm": warm_s}
    plain = np.nonzero((a != b).any(axis=1))[0]
    assert np.array_equal(got, plain), "merkle diff differs from a compare"
    out["merkle"] = {"leaves": n, "changed": int(len(plain))}

    # rateless coded symbols: device engine vs host engine, incrementally
    d = rng.integers(0, 256, (sizes["rateless_digests"], 32), dtype=np.uint8)
    h_sym, d_sym = CodedSymbols(d, engine="host"), \
        CodedSymbols(d, engine="device")
    for m in sizes["rateless_symbols"]:
        assert np.array_equal(h_sym.extend(m), d_sym.extend(m)), \
            f"rateless device symbols differ from host at m={m}"
    out["rateless"] = {"digests": len(d),
                       "symbols": sizes["rateless_symbols"][-1]}

    if not dry:
        out["mosaic"] = _mosaic_kernels()
        assert all(out["mosaic"].values()), \
            f"kernels not compiled by Mosaic: {out['mosaic']}"
        assert list(out["mosaic"]) == list(PALLAS_KERNELS)
    _child_epilogue(out, t0)


def child_mesh(seed: int, sizes: dict, dry: bool) -> None:
    t0 = time.monotonic()
    device = _child_prologue(dry)
    import jax
    import numpy as np

    sys.path.insert(0, REPO)
    import __graft_entry__ as graft

    from dat_replication_protocol_tpu.parallel import make_mesh
    from dat_replication_protocol_tpu.parallel.mesh import sharded_hash_engine

    n = device["device_count"]
    while n & (n - 1):
        n -= 1
    if n < 2:
        _child_epilogue({"device": device,
                         "skipped": f"{n} device visible"}, t0)
        return
    graft.dryrun_multichip(n)
    mesh = make_mesh(n)
    rng = np.random.default_rng([seed, 4])
    payloads = [rng.bytes(1024) for _ in range(8 * n)]
    shard = jax.device_put(np.zeros((8 * n, 4), np.uint32),
                           jax.sharding.NamedSharding(
                               mesh, jax.sharding.PartitionSpec("data")))
    homes = {s.device.id for s in shard.addressable_shards}
    assert len(homes) == n, f"shards sit on {len(homes)} of {n} devices"
    assert sharded_hash_engine(mesh)(payloads)() == [_h(p) for p in payloads]
    _child_epilogue({"device": device, "mesh_devices": n,
                     "shard_devices": sorted(homes)}, t0)


def stage_child(stage: str, seed: int, dry: bool) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", stage,
           "--seed", str(seed)] + (["--dry-run"] if dry else [])
    proc = subprocess.run(cmd, cwd=REPO, stdin=subprocess.DEVNULL,
                          stdout=subprocess.PIPE, text=True, timeout=1500)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    need(stage, proc.returncode == 0 and lines,
         f"child exited {proc.returncode} (its stderr is above)")
    rep = json.loads(lines[-1])
    if not dry:
        need(stage, rep["device"]["platform"] == "tpu",
             f"child ran on {rep['device']}")
    return rep


# ---------------------------------------------------------------------------


def native_builds() -> set:
    d = os.path.join(REPO, "dat_replication_protocol_tpu", "native", "_build")
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def versions() -> dict:
    from importlib import metadata

    out = {"python": sys.version.split()[0]}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--only", default=None, metavar="STAGE[,STAGE]",
                    help=f"run a subset of {','.join(STAGES)}")
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes on a CPU host, device checks skipped; "
                         "debugs this script, proves nothing about the chip")
    ap.add_argument("--child", choices=("ops", "mesh"), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sizes = DRY if args.dry_run else FULL
    if args.child:
        {"ops": child_ops, "mesh": child_mesh}[args.child](
            args.seed, sizes, args.dry_run)
        return 0

    built_before = native_builds()
    try:
        import dat_replication_protocol_tpu  # noqa: F401 — the program
    except ImportError as e:
        print(f"chip_smoke: the package is not beside this script: {e}",
              file=sys.stderr)
        return 2
    assert "jax" not in sys.modules, "the parent must stay off jax"
    only = set(args.only.split(",")) if args.only else set(STAGES)
    unknown = only - set(STAGES)
    if unknown:
        ap.error(f"unknown stage(s) {sorted(unknown)}")

    t_start = time.monotonic()
    report: dict = {"seed": args.seed, "dry_run": args.dry_run,
                    "versions": versions(), "reduced": REDUCED, "stages": {}}
    say(f"chip_smoke: versions {json.dumps(report['versions'])}")
    for cut in REDUCED:
        say(f"chip_smoke: reduced: {cut}")
    device = None

    def done(stage: str, rep: dict) -> None:
        nonlocal device
        device = device or rep["device"]
        report["stages"][stage] = rep
        say(f"chip_smoke[{stage}] {json.dumps(rep)}")

    try:
        if "plain" in only:
            done("plain", stage_plain(args.seed, sizes, args.dry_run))
        if "hub" in only:
            done("hub", stage_hub(args.seed, sizes, args.dry_run))
        if "second" in only:
            done("second", stage_plain(
                args.seed, sizes, args.dry_run, second=True,
                first=report["stages"].get("plain")))
        if "ops" in only:
            done("ops", stage_child("ops", args.seed, args.dry_run))
        if "mesh" in only:
            if device is not None and device["device_count"] < 2:
                say("chip_smoke[mesh] not run: 1 device visible")
            else:
                # the child reports what is visible (and skips itself
                # on one device) — the parent cannot ask jax
                rep = stage_child("mesh", args.seed, args.dry_run)
                if "skipped" in rep:
                    say(f"chip_smoke[mesh] not run: {rep['skipped']}")
                else:
                    done("mesh_dryrun", rep)
                    done("mesh", stage_hub(args.seed, sizes, args.dry_run,
                                           mesh=True))
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED {e}", file=sys.stderr, flush=True)
        return 1
    except Exception as e:  # noqa: BLE001 — any stage error fails the run
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1

    report["native_built_in_this_run"] = sorted(
        native_builds() - built_before)
    report["seconds"] = round(time.monotonic() - t_start, 1)
    say(f"chip_smoke: native libraries built in this run: "
        f"{report['native_built_in_this_run']}")
    say(f"chip_smoke: {len(report['stages'])} stage(s) in "
        f"{report['seconds']}s")
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "chip_smoke.json"),
              "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    final = {"device": {"platform": device["platform"],
                        "kind": device["device_kind"],
                        "count": device["device_count"]}}
    if args.dry_run:
        final = {"dry_run": True, "stages": sorted(report["stages"]), **final}
    else:
        final = {"ok": True, **final}
    say(json.dumps(final))
    return 0


if __name__ == "__main__":
    sys.exit(main())
