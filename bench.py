"""Benchmark harness — all five BASELINE.json configs.

Prints exactly ONE JSON line on stdout:

    {"metric": "blake2b_batched_blob_hash_throughput", "value": N,
     "unit": "GiB/s", "vs_baseline": N, "backend": ..., "configs": {...}}

The headline metric is config 3 (the 50 GiB/s north-star target);
``configs`` carries one result object per BASELINE config:

  1 roundtrip     sessions/sec of the test/basic.js encode->decode flow
  2 replay        rows/sec of 1M-row change-log replay (native engine)
  3 hash          GiB/s of batched BLAKE2b blob hashing   (target 50)
  4 cdc           GiB/s of content-defined chunking incl. host select
  5 merkle_diff   entries/sec of two-snapshot tree diff    (target 10M)
  6 resume        ms from transport fault to first re-delivered frame
                  (checkpoint export -> reconnect -> redelivery; ROBUSTNESS.md)
  7 wire_batch    rows/s per-record vs columnar ChangeBatch framing A/B
  8 fused_e2e     GiB/s bytes->digests: fused single-pass route vs the
                  two-pass route (min-of-reps A/B; ISSUE 7)
  9 hub_soak      N concurrent sessions on ONE shared ReplicationHub:
                  aggregate GiB/s + per-session fairness (min/median
                  session throughput ratio; ISSUE 8)
  10 fanout       one-to-many broadcast: peers x delivered-MiB/s matrix
                  with hash-once counter proof + stalled-peer p99
                  isolation (ISSUE 9)
  11 reconcile_rateless  anti-entropy A/B at k in {10, 1000, 100000} on
                  1M+1M divergent replicas: rateless coded symbols vs
                  the sketch-table exchange vs the tree descent — wire
                  bytes and wall clock per arm (ISSUE 10)
  12 snapshot_bootstrap  content-addressed snapshot transfer: 2%-stale
                  joiner wire ratio vs cold full transfer (target
                  <= 0.05 at 1 GiB), 8-joiner cold flash crowd with
                  hash-once counter proof (hash_ratio 1.0), and a
                  torn-wire exactly-once resume arm (ISSUE 12)
  13 wire_pump    kernel-bypass transport pump A/B: e2e bytes->digest
                  over a real socket, native batched-syscall pump vs
                  the Python reference, plus hub aggregate vs session
                  count 1/4/16 (the GIL-flatness probe; ISSUE 14)
  14 gossip_converge  N-replica epidemic anti-entropy: rounds/seconds
                  to byte-identical replicas and total wire bytes vs
                  divergence size at N in {4, 16, 64} (ISSUE 15)
  15 edge_scaling  C10k control plane: 1/100/1k/10k concurrent
                  mixed-QoS sessions through ONE event-driven edge
                  loop — peak table occupancy, finish-flood
                  sessions/s, p99, admission/shed counts (must stay
                  zero on a properly sized hub; ISSUE 17).  Not in
                  the default set: request with BENCH_CONFIGS=15
                  (the 10k cohort spawns a client subprocess)

One process per chip: jax initialises once, in this process, before
any config runs; no child process ever needs the device.  The artifact
names the device every figure ran on (``device``: platform, device_kind,
device_count as jax reports them).  Configs 3, 4 and 5 carry device
metrics and refuse to run on a CPU backend unless ``BENCH_PLATFORM=cpu``
asked for a functional CPU run (their metric is then renamed
``cpu_functional_*``).  Each config runs in its own try/except so one
failure cannot blank the others, but the run exits non-zero if any
requested config errored, the backend failed to initialise, or the
deadline fired.  ``--quick`` is small on every backend.

Env knobs: BENCH_ITEMS / BENCH_ITEM_MIB / BENCH_CHUNK (config 3),
BENCH_REPLAY_ROWS, BENCH_CDC_MIB / BENCH_CDC_REPS, BENCH_MERKLE_LOG2,
BENCH_ROUNDTRIPS, BENCH_RESUME_ROWS / BENCH_RESUME_REPS (config 6),
BENCH_CONFIGS (comma list, default "1,2,3,4,5,6,7,8,9,10,11"),
BENCH_RECONCILE_N / BENCH_RECONCILE_KS (config 11),
BENCH_FUSED_MIB / BENCH_FUSED_REPS / BENCH_FUSED_DEVICE (config 8),
BENCH_HUB_SESSIONS / BENCH_HUB_ROWS / BENCH_HUB_BLOB_KIB /
BENCH_HUB_MESH (config 9), BENCH_FANOUT_ROWS / BENCH_FANOUT_BLOB_KIB /
BENCH_FANOUT_PEERS / BENCH_FANOUT_STALL_S (config 10),
BENCH_SNAPSHOT_MIB / BENCH_SNAPSHOT_JOINERS / BENCH_SNAPSHOT_STALE
(config 12), BENCH_PUMP_MIB / BENCH_PUMP_REPS / BENCH_PUMP_SESSIONS
(config 13), BENCH_GOSSIP_N / BENCH_GOSSIP_RECORDS /
BENCH_GOSSIP_DIVERGENCE (config 14), BENCH_EDGE_N (config 15).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
import traceback


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _timed_reps(fenced_run, reps: int) -> list[float]:
    """Time ``reps`` calls individually; caller takes the median.

    Each call must fence its own completion (device configs end in a
    small D2H).  Median-of-reps is the headline on device configs: the
    dev chip is shared, and one congestion spike in one rep should not
    misprice a kernel (identical code measured 9-21 GiB/s across one
    congested afternoon).  The aggregate over sum(dts) is reported
    alongside for transparency.
    """
    dts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fenced_run()
        dts.append(time.perf_counter() - t0)
    return dts


def _timed_reps_pipelined(dispatch, fence, reps: int, depth: int = 2):
    """Sustained per-rep timing with ``depth`` reps in flight.

    Serial fence-per-rep timing bills the host's fence round-trip
    against every rep.  Here rep k+1 is dispatched before rep k is
    fenced, so the fence rides under the next rep's device compute;
    per-rep spans are fence-to-fence, i.e. steady-state device cost.

    EVERY rep's output is still individually fetched — only the host's
    wait overlaps.  ``BENCH_SERIAL_FENCE=1`` restores the serial
    methodology.
    """
    if os.environ.get("BENCH_SERIAL_FENCE") == "1":
        return _timed_reps(lambda: fence(dispatch()), reps)
    depth = max(1, depth)
    # priming rep, fenced untimed: without it the FIRST timed span has
    # no older rep completing under it and eats the full fence RTT the
    # helper exists to hide — at reps=2 that biases the median ~25%
    primer = dispatch()
    inflight = [dispatch() for _ in range(min(depth, reps))]
    launched = len(inflight)
    fence(primer)
    dts = []
    t_prev = time.perf_counter()
    while inflight:
        fence(inflight.pop(0))
        now = time.perf_counter()
        dts.append(now - t_prev)
        t_prev = now
        if launched < reps:
            inflight.append(dispatch())
            launched += 1
    return dts


def _peak_span(dts: list) -> float:
    """Fastest CREDIBLE span for the diagnostic peak fields: under
    pipelined fencing, a stall in span k lets rep k+1 finish on device
    early, so span k+1 collapses toward the bare fence RTT — faster
    than the hardware ever ran.  Two guards (advisor r4): spans under
    half the median are queue-drain artifacts, and a span whose
    PREDECESSOR was an outlier-high (>1.5x median) is still partially
    drain-compressed even inside the 0.5–1.0x band — exclude both.
    The peak_* fields remain upper bounds on uncontended capability,
    never headlines (the median is the headline)."""
    med = statistics.median(dts)
    cred = [d for i, d in enumerate(dts)
            if d >= 0.5 * med and (i == 0 or dts[i - 1] <= 1.5 * med)]
    return min(cred) if cred else med


def _fence_mode() -> str:
    """Recorded in every device-config result: pipelined and serial
    fence numbers are different measurements, so artifact comparisons
    must not mix them blindly."""
    return "serial" if os.environ.get("BENCH_SERIAL_FENCE") == "1" else "pipelined"


def _env_int(name, default):
    return int(os.environ.get(name, default))


# ---------------------------------------------------------------------------
# wire cost plane capture (ISSUE 20): goodput_ratio / overhead_ratio fields
# for configs 7/10/11/12/14, read off the WireCostBoard with the plane lit —
# the same watermarks `obs fleet` gates in production, so the checked-in
# snapshot is the wire_ratio baseline ROADMAP item 4's compression tier
# will be diffed against
# ---------------------------------------------------------------------------


def _wirecost_ratios(*links) -> tuple:
    """(goodput_ratio, overhead_ratio) aggregated over the named board
    links (both directions), or over EVERY link when none are named —
    payload-weighted, from the live WireCostBoard ledger."""
    from dat_replication_protocol_tpu.obs.wirecost import WIRECOST

    snap = WIRECOST.snapshot()["links"]
    payload = framing = total = 0
    for name, rec in snap.items():
        if links and name.split("|", 1)[0] not in links:
            continue
        payload += rec["payload_bytes"]
        framing += rec["framing_bytes"]
        total += rec["ledger_bytes"]
    if not total:
        return None, None
    return round(payload / total, 5), round(framing / total, 5)


def _wirecost_decode_ratios(wire: bytes) -> tuple:
    """(goodput_ratio, overhead_ratio) of one recorded wire: the bytes
    are replayed through a LIT session decoder and the board's per-link
    watermarks are the ratios.  Obs state is saved/restored; the board
    is reset so the ledger holds exactly this wire."""
    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics
    from dat_replication_protocol_tpu.obs.wirecost import WIRECOST

    was_on = obs_metrics.OBS.on
    obs_metrics.enable()
    WIRECOST.reset_for_tests()
    try:
        dec = protocol.decode()
        dec.on_error(lambda e: None)
        step = 1 << 20
        for off in range(0, len(wire), step):
            dec.write(wire[off:off + step])
        return _wirecost_ratios("session")
    finally:
        WIRECOST.reset_for_tests()
        obs_metrics.OBS.on = was_on


# ---------------------------------------------------------------------------
# config 1: test/basic.js-shaped roundtrip (reference: test/basic.js:1-127)
# ---------------------------------------------------------------------------


def bench_roundtrip(quick: bool, backend: str) -> dict:
    import dat_replication_protocol_tpu as protocol

    n = _env_int("BENCH_ROUNDTRIPS", 200 if quick else 2000)

    def one_session():
        got = []
        enc = protocol.encode()
        dec = protocol.decode()
        dec.change(lambda ch, done: (got.append(ch.key), done()))
        dec.blob(lambda blob, done: blob.collect(lambda b: (got.append(b), done())))
        enc.change({"key": "a", "change": 1, "from_": 0, "to": 1, "value": b"v"})
        ws = enc.blob(12)
        ws.write(b"hello ")
        ws.end(b"world!")
        enc.change({"key": "b", "change": 2, "from_": 1, "to": 2})
        enc.finalize()
        protocol.pipe(enc, dec)
        assert got == ["a", b"hello world!", "b"], got

    one_session()  # correctness gate + warmup
    t0 = time.perf_counter()
    for _ in range(n):
        one_session()
    dt = time.perf_counter() - t0

    # bulk decode rate: a change+blob log pushed through Decoder.write in
    # 256 KiB chunks (the native-indexed hot path; round-2 verdict item 5)
    from dat_replication_protocol_tpu.wire.change_codec import encode_change
    from dat_replication_protocol_tpu.wire.framing import (
        TYPE_BLOB,
        TYPE_CHANGE,
        frame,
    )

    rows = _env_int("BENCH_DECODE_ROWS", 20_000 if quick else 400_000)
    block_n = min(rows, 4096)
    parts = []
    for i in range(block_n):
        parts.append(frame(TYPE_CHANGE, encode_change({
            "key": f"key-{i:07d}", "change": i, "from": i, "to": i + 1,
            "value": b"v" * (i % 48),
        })))
        if i % 64 == 0:
            parts.append(frame(TYPE_BLOB, b"B" * 512))
    block = b"".join(parts)
    reps = -(-rows // block_n)
    wire = block * reps
    nframes = (block_n + -(-block_n // 64)) * reps

    dec = protocol.decode()
    counted = {"changes": 0}
    dec.change(lambda ch, done: (counted.__setitem__(
        "changes", counted["changes"] + 1), done()))
    t0 = time.perf_counter()
    for off in range(0, len(wire), 1 << 18):
        dec.write(wire[off : off + (1 << 18)])
    dec.end()
    ddt = time.perf_counter() - t0
    assert counted["changes"] == block_n * reps, counted
    decode_mib_s = len(wire) / ddt / (1 << 20)
    log(
        f"bench[roundtrip]: bulk decode {len(wire) / (1 << 20):.1f} MiB in "
        f"{ddt:.3f}s = {decode_mib_s:.1f} MiB/s ({nframes / ddt:,.0f} frames/s)"
    )

    # blob-dominated wire: byte throughput of the slicing fast path
    blob_frame = frame(TYPE_BLOB, b"B" * (256 << 10))
    blob_wire = blob_frame * (8 if quick else 64)
    dec2 = protocol.decode()
    seen = {"blobs": 0}
    dec2.blob(lambda blob, done: (
        blob.on_data(lambda _c: None),
        blob.on_end(lambda: (seen.__setitem__("blobs", seen["blobs"] + 1),
                             done())),
    ))
    t0 = time.perf_counter()
    for off in range(0, len(blob_wire), 1 << 18):
        dec2.write(blob_wire[off : off + (1 << 18)])
    dec2.end()
    bdt = time.perf_counter() - t0
    assert seen["blobs"] == len(blob_wire) // len(blob_frame)
    blob_mib_s = len(blob_wire) / bdt / (1 << 20)
    log(f"bench[roundtrip]: blob decode {blob_mib_s:.0f} MiB/s")
    return {
        "metric": "session_roundtrip_rate",
        "value": round(n / dt, 1),
        "unit": "sessions/s",
        "vs_baseline": None,
        "decode_mib_s": round(decode_mib_s, 1),
        "decode_frames_s": round(nframes / ddt, 0),
        "decode_blob_mib_s": round(blob_mib_s, 1),
    }


# ---------------------------------------------------------------------------
# config 2: 1M-row change-log replay (native framing + proto decode)
# ---------------------------------------------------------------------------


def bench_replay(quick: bool, backend: str) -> dict:
    import numpy as np

    from dat_replication_protocol_tpu.runtime import native, replay
    from dat_replication_protocol_tpu.wire.change_codec import Change, encode_change
    from dat_replication_protocol_tpu.wire.framing import TYPE_CHANGE, frame

    rows = _env_int("BENCH_REPLAY_ROWS", 20_000 if quick else 1_000_000)
    # build the log from a repeated block of distinct records: encoding
    # 1M rows one-by-one in Python would dominate setup time
    block_n = min(rows, 4096)
    recs = [
        Change(
            key=f"key-{i:07d}",
            change=i,
            from_=i,
            to=i + 1,
            value=b"v" * (i % 48),
            subset="s" if i % 3 else None,
        )
        for i in range(block_n)
    ]
    block = b"".join(frame(TYPE_CHANGE, encode_change(c)) for c in recs)
    reps = -(-rows // block_n)
    log_buf = np.frombuffer(block * reps, dtype=np.uint8)
    total_rows = block_n * reps

    t0 = time.perf_counter()
    cols, frames = replay.replay_log(log_buf)
    dt = time.perf_counter() - t0
    assert len(cols) == total_rows

    # the inverse path: bulk log construction (native columnar encoder),
    # measured over enough rows that the interval is timing-stable.
    # Fed as dicts so encode_rows_s keeps billing the per-row
    # from_dict conversion the metric has always included
    dicts = [c.to_dict() for c in recs]
    replay.encode_change_log(dicts[:64])  # warm the path
    enc_reps = max(1, min(total_rows, 100_000) // block_n)
    big = dicts * enc_reps
    t0 = time.perf_counter()
    wire = replay.encode_change_log(big)
    edt = time.perf_counter() - t0
    assert wire == block * enc_reps
    enc_rows = len(big)

    # columnar re-encode (replay_log's exact inverse, zero Python/row):
    # the decoded columns of the full log straight back to wire bytes
    t0 = time.perf_counter()
    cwire = replay.encode_change_columns(cols)
    cdt = time.perf_counter() - t0
    assert len(cwire) == log_buf.nbytes
    return {
        "metric": "change_log_replay_rate",
        "value": round(total_rows / dt, 0),
        "unit": "rows/s",
        "vs_baseline": None,
        "native": native.available(),
        "rows": total_rows,
        "reduced_config": total_rows < 1_000_000,
        "full_config": "1M rows (BASELINE config 2)",
        "log_mib": round(log_buf.nbytes / (1 << 20), 1),
        "encode_rows_s": round(enc_rows / edt, 0),
        "encode_columns_rows_s": round(total_rows / cdt, 0),
    }


# ---------------------------------------------------------------------------
# config 3: batched BLAKE2b blob hashing (headline; target >= 50 GiB/s)
# ---------------------------------------------------------------------------


def bench_hash(quick: bool, backend: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dat_replication_protocol_tpu.ops.blake2b import BLOCK_BYTES, blake2b_packed

    use_pallas = backend == "tpu"
    if quick:
        d_items, d_mib, d_chunk = (2048, 1, 2048) if use_pallas else (32, 0.125, 32)
    elif use_pallas:
        d_items, d_mib, d_chunk = 10240, 1, 4096
    else:
        d_items, d_mib, d_chunk = 64, 0.125, 32
    items = _env_int("BENCH_ITEMS", d_items)
    item_mib = float(os.environ.get("BENCH_ITEM_MIB", d_mib))
    chunk = min(_env_int("BENCH_CHUNK", d_chunk), items)
    if use_pallas:
        chunk = max(1024, chunk // 1024 * 1024)  # pallas tiles 1024 items

    item_bytes = max(BLOCK_BYTES, int(item_mib * (1 << 20)) // BLOCK_BYTES * BLOCK_BYTES)
    nblocks = item_bytes // BLOCK_BYTES
    reps = max(1, -(-items // chunk))  # ceil: honor the full item count
    log(
        f"bench[hash]: pallas={use_pallas} items={reps * chunk} x {item_bytes} B "
        f"(chunk={chunk}, reps={reps})"
    )

    if not use_pallas:
        from dat_replication_protocol_tpu.runtime import native as _native
        from dat_replication_protocol_tpu.utils.routing import prefer_host

        if prefer_host("DAT_DEVICE_HASH") and _native.available():
            # the engine the routing layer actually picks on a CPU host
            # ("batch or stay home"), measured THROUGH the routed entry
            # point (_host_hash_batch: join + native C pass + per-row
            # bytes) so the number is what a backend='tpu' session pays
            # per batch — not the raw C kernel.  The XLA-scan number
            # stays alongside for cross-round continuity but represents
            # nothing a user would run here.
            from dat_replication_protocol_tpu.backend.tpu_backend import (
                _host_hash_batch,
            )

            hb = _env_int("BENCH_HOST_HASH_MIB", 32 if quick else 256) << 20
            hitems = max(64, hb // item_bytes)  # >= 64: the router's own
            # native-path threshold
            rng0 = np.random.default_rng(3)
            payloads = [
                rng0.integers(0, 256, item_bytes, dtype=np.uint8).tobytes()
                for _ in range(hitems)
            ]
            _host_hash_batch(payloads[:64])  # warm (.so build/load)
            t0 = time.perf_counter()
            digs0 = _host_hash_batch(payloads)
            hdt = time.perf_counter() - t0
            assert len(digs0) == hitems
            host_gib_s = hitems * item_bytes / hdt / (1 << 30)
            host_fields = {"host_items": hitems,
                           "host_volume_gib":
                               round(hitems * item_bytes / (1 << 30), 3)}
            log(f"bench[hash]: routed host engine {host_gib_s:.3f} GiB/s "
                f"({hitems} x {item_bytes} B)")
        else:
            host_gib_s = None
    else:
        host_gib_s = None

    kh, kl = jax.random.split(jax.random.PRNGKey(0))
    variant = "xla-scan"
    if use_pallas:
        from dat_replication_protocol_tpu.ops.blake2b_pallas import blake2b_native

        shape = (nblocks, 16, 8, chunk // 8)
        mh = jax.random.bits(kh, shape, dtype=jnp.uint32)
        ml = jax.random.bits(kl, shape, dtype=jnp.uint32)
        lengths = jnp.full((8, chunk // 8), item_bytes, dtype=jnp.uint32)
        jax.block_until_ready((mh, ml))

        # self-select the kernel variant: one warmed+fenced calibration
        # rep each (register-resident vs VMEM-resident working vectors
        # rank differently depending on the chip's scheduler; the bench
        # should capture the best configuration, not a guess)
        t0 = time.perf_counter()
        best = None
        golden = None  # digest output of a TESTED variant: others must
        # reproduce it.  Every composition except (False, True) has a
        # CPU byte-exactness test (test_blake2b_pallas.py), so any of
        # those may anchor; (False, True) is covered ONLY by this guard
        # and never anchors.
        cpu_tested = {(False, False), (True, False), (True, True)}
        # (False, True) runs LAST: it is the only composition without a
        # CPU byte-exactness test, so it must never anchor golden — and
        # visiting it after every anchor-capable variant means a single
        # baseline compile failure cannot permanently skip it
        for vs, sl in ((False, False), (True, False), (True, True),
                       (False, True)):
            kern = lambda vs=vs, sl=sl: blake2b_native(  # noqa: E731
                mh, ml, lengths, vmem_state=vs, state_loads=sl)
            try:
                if golden is None and (vs, sl) not in cpu_tested:
                    log(f"bench[hash]: no tested baseline compiled yet; "
                        f"skipping unanchorable variant vmem={vs} sloads={sl}")
                    continue
                hh, hl = kern()  # compile + warm
                probe = (np.asarray(hh), np.asarray(hl))  # FULL digests:
                # a lane-partial miscompile must not slip past the guard
                if golden is None:
                    golden = probe
                elif not (np.array_equal(golden[0], probe[0])
                          and np.array_equal(golden[1], probe[1])):
                    # never self-select a miscompiled variant for the
                    # headline number, however fast it runs
                    log(f"bench[hash]: variant vmem={vs} sloads={sl} "
                        f"DIGEST MISMATCH vs baseline; skipped")
                    continue
                # median of 3, pipeline-fenced: one rep can misprice by
                # >2x on the shared chip and would silently pick the
                # wrong kernel; serial fencing would additionally bury
                # variant deltas under the ~66 ms link RTT
                cals = _timed_reps_pipelined(
                    kern,
                    lambda o: (np.asarray(o[0][:1, :1]),
                               np.asarray(o[1][:1, :1])),
                    3,
                )
                cal = statistics.median(cals)
            except Exception as e:
                log(f"bench[hash]: variant vmem={vs} sloads={sl} failed ({e})")
                continue
            log(f"bench[hash]: calibrate vmem={vs} sloads={sl}: "
                f"{cal:.3f}s/rep (median of 3)")
            if best is None or cal < best[1]:
                best = (kern, cal, vs, sl)
        if best is None:
            raise RuntimeError("no hash kernel variant ran")
        run = best[0]
        variant = f"pallas(vmem_state={best[2]},state_loads={best[3]})"
        log(
            f"bench[hash]: compile+calibrate {time.perf_counter() - t0:.1f}s "
            f"-> {variant}"
        )
    else:
        shape = (chunk, nblocks, 16)
        mh = jax.random.bits(kh, shape, dtype=jnp.uint32)
        ml = jax.random.bits(kl, shape, dtype=jnp.uint32)
        lengths = jnp.full((chunk,), item_bytes, dtype=jnp.uint32)
        run = lambda: blake2b_packed(mh, ml, lengths)  # noqa: E731
        jax.block_until_ready((mh, ml))
        t0 = time.perf_counter()
        np.asarray(run()[0])
        log(f"bench[hash]: compile+first-run {time.perf_counter() - t0:.1f}s")

    # completion barrier: fetch a tiny slice of every rep's output.  The
    # digests themselves stay in HBM — their consumer is the on-device
    # Merkle stage (batch/feed.leaves_from_columns ->
    # ops.merkle.build_tree), not the host.
    def fence(out):
        hh, hl = out
        np.asarray(hh[:1, :1])
        np.asarray(hl[:1, :1])

    rep_dts = _timed_reps_pipelined(run, fence, reps)
    dt = sum(rep_dts)
    total = reps * chunk * item_bytes
    gib_s = (chunk * item_bytes) / statistics.median(rep_dts) / (1 << 30)
    log(
        f"bench[hash]: {total / (1 << 30):.1f} GiB in {dt:.3f}s = "
        f"{gib_s:.2f} GiB/s median ({total / dt / (1 << 30):.2f} aggregate)"
    )

    # honest end-to-end variant: host log buffer -> pack_ragged -> H2D ->
    # digests -> D2H, the batch/feed.py:hash_extents path.  This figure
    # characterizes the host+transfer pipeline, not the kernel;
    # h2d_mib_s is recorded alongside so the artifact shows the link it
    # was measured over.
    from dat_replication_protocol_tpu.batch.feed import hash_extents

    # sized so the feed layer's pipelining actually engages: with
    # pipeline_bytes=16 MiB the 1024-item batch splits into multiple
    # chunks whose uploads stream under earlier chunks' compute (on the
    # TPU path the pallas item floor makes the chunks wider — still >= 2)
    e2e_items = 128 if quick else 1024
    e2e_item = 1 << 18  # 256 KiB
    e2e_pipe = {"pipeline_bytes": 16 << 20}
    buf = np.random.default_rng(1).integers(
        0, 256, e2e_items * e2e_item, dtype=np.uint8
    )
    offs = np.arange(e2e_items, dtype=np.int64) * e2e_item
    lens = np.full(e2e_items, e2e_item, dtype=np.int64)
    hash_extents(buf, offs, lens, **e2e_pipe)  # warmup/compile at the FULL
    # batch shape: a smaller warmup would leave the timed call paying a
    # fresh jit specialization and mislabel compile time as pipeline time
    t0 = time.perf_counter()
    digs = hash_extents(buf, offs, lens, **e2e_pipe)
    e2e_dt = time.perf_counter() - t0
    assert len(digs) == e2e_items
    e2e_gib_s = buf.nbytes / e2e_dt / (1 << 30)

    # session-level digest rate: blob frames through the backend='tpu'
    # decoder, digests included — the engine the routing layer actually
    # picks on THIS host (device batches on an accelerator, native/hashlib
    # on a CPU host; round-3 verdict weak #4's acceptance measure)
    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.wire.framing import TYPE_BLOB as _TB
    from dat_replication_protocol_tpu.wire.framing import frame as _frame

    blob_frame = _frame(_TB, b"B" * (256 << 10))
    sess_wire = blob_frame * (16 if quick else 128)
    dec = protocol.decode(backend="tpu")
    counted = {"n": 0}
    dec.on_digest(lambda k, s, d: counted.__setitem__("n", counted["n"] + 1))
    dec.blob(lambda blob, done: (blob.on_data(lambda _c: None),
                                 blob.on_end(done)))
    t0 = time.perf_counter()
    for off in range(0, len(sess_wire), 1 << 18):
        dec.write(sess_wire[off:off + (1 << 18)])
    dec.end()
    sdt = time.perf_counter() - t0
    assert counted["n"] == len(sess_wire) // len(blob_frame)
    session_mib_s = len(sess_wire) / sdt / (1 << 20)
    log(f"bench[hash]: session digest path {session_mib_s:.0f} MiB/s "
        f"({counted['n']} blobs)")

    probe_bytes = min(32 << 20, buf.nbytes)
    x = jnp.asarray(buf[:probe_bytes])
    t0 = time.perf_counter()
    np.asarray(x[:8])
    h2d = (probe_bytes / (1 << 20)) / (time.perf_counter() - t0)
    # overlap factor: e2e throughput as a fraction of the measured link —
    # with H2D staged under compute (batch/feed pipelining) a link-bound
    # path should sit near 1.0; round 3 measured 0.03-0.3 with nothing
    # overlapped
    e2e_vs_link = (e2e_gib_s * 1024) / h2d
    log(
        f"bench[hash]: e2e host->digest {e2e_gib_s:.3f} GiB/s "
        f"({buf.nbytes >> 20} MiB; link h2d ~{h2d:.0f} MiB/s; "
        f"{e2e_vs_link:.2f}x link)"
    )
    out = {
        "metric": "blake2b_batched_blob_hash_throughput",
        "value": round(gib_s, 3),
        "unit": "GiB/s",
        "vs_baseline": round(gib_s / 50.0, 4),
        # below-config shapes must say so in-band,
        # not rely on the reader cross-checking items x item_bytes
        "reduced_config": total < (10240 << 20),
        "full_config": "10240 x 1 MiB (BASELINE config 3)",
        "aggregate_gib_s": round(total / dt / (1 << 30), 3),
        # best credible rep: on the shared dev chip this approximates
        # the uncontended rate (diagnostic only; the median stays the
        # headline; see _peak_span for the queue-drain guard)
        "peak_gib_s": round(
            (chunk * item_bytes) / _peak_span(rep_dts) / (1 << 30), 3
        ),
        "fence": _fence_mode(),
        "kernel_variant": variant,
        "e2e_host_gib_s": round(e2e_gib_s, 3),
        "session_digest_mib_s": round(session_mib_s, 1),
        "h2d_mib_s": round(h2d, 1),
        "e2e_vs_link": round(e2e_vs_link, 3),
        "items": reps * chunk,
        "item_bytes": item_bytes,
    }
    if host_gib_s is not None:
        # headline = the routed engine on this host; the scan number
        # stays alongside for cross-round continuity
        out["value"] = round(host_gib_s, 3)
        out["vs_baseline"] = round(host_gib_s / 50.0, 4)
        out["kernel_variant"] = "native-host"
        out["xla_scan_gib_s"] = round(gib_s, 3)
        # the peak was measured on the scan path, not the routed host
        # engine — rename it alongside the scan median so peak < value
        # can't read as nonsense
        out["xla_scan_peak_gib_s"] = out.pop("peak_gib_s")
        out.update(host_fields)  # the host run's own volume/provenance
    return out


# ---------------------------------------------------------------------------
# config 4: content-defined chunking over a large blob (10 GiB volume)
# ---------------------------------------------------------------------------


def bench_cdc(quick: bool, backend: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dat_replication_protocol_tpu.ops import rabin

    on_tpu = backend == "tpu"
    if quick:
        slab_mib, reps = (64, 2) if on_tpu else (2, 2)
    elif on_tpu:
        # 10 GiB total volume via a 2 GiB slab (the per-call cap):
        # per-slab dispatch and fence cost is fixed, so fewer, larger
        # slabs are better until the cap
        slab_mib, reps = 2048, 5
    else:
        slab_mib, reps = 8, 2
    slab_mib = _env_int("BENCH_CDC_MIB", slab_mib)
    reps = _env_int("BENCH_CDC_REPS", reps)
    slab_bytes = slab_mib << 20
    avg_bits = 13

    if not on_tpu:
        from dat_replication_protocol_tpu.runtime import native
        from dat_replication_protocol_tpu.utils.routing import prefer_host

        # the branch label must match what chunk_stream will actually
        # route to: prefer_host consults the same decision (and the
        # DAT_DEVICE_CDC override) chunk_stream does
        if prefer_host("DAT_DEVICE_CDC") and native.available():
            # the engine the routing layer actually picks on a CPU host:
            # the native C gear scan + native greedy select through
            # chunk_stream ("batch or stay home" — the XLA-scan path
            # measures ~0.0002 GiB/s here and represents nothing a user
            # would run)
            host_mib = _env_int("BENCH_CDC_HOST_MIB", 64 if quick else 256)
            data = np.random.default_rng(7).integers(
                0, 256, host_mib << 20, dtype=np.uint8
            )
            rabin.chunk_stream(data[: 4 << 20], avg_bits=avg_bits)  # warm
            t0 = time.perf_counter()
            cuts = rabin.chunk_stream(data, avg_bits=avg_bits)
            dt = time.perf_counter() - t0
            gib_s = data.nbytes / dt / (1 << 30)
            log(f"bench[cdc]: native host engine {gib_s:.2f} GiB/s "
                f"({len(cuts)} chunks / {host_mib} MiB)")
            return {
                "metric": "cdc_chunking_throughput",
                "value": round(gib_s, 3),
                "unit": "GiB/s",
                "vs_baseline": None,
                "volume_gib": round(data.nbytes / (1 << 30), 2),
                "engine": "native-host",
                "chunks": len(cuts),
                "reduced_config": data.nbytes < (10 << 30),
                "full_config": "10 GiB blob (BASELINE config 4)",
            }

    # the blob lives in HBM (the framework's hot path hashes/chunks data
    # that the feed layer already staged on device); the timed loop is
    # kernel + on-device sparse extraction + O(candidates) D2H + greedy
    # min/max select (native C) — everything a consumer of cut offsets
    # pays.  Mirrors the hash bench's device-resident methodology.
    words = jax.random.bits(
        jax.random.PRNGKey(7), (slab_bytes // 4,), dtype=jnp.uint32
    )
    jax.block_until_ready(words)

    def begin():
        return rabin.candidates_begin(
            words, slab_bytes, avg_bits, thin_bits=avg_bits - 2
        )

    def finish(collect):
        return rabin._greedy_select(
            collect(), slab_bytes, 1 << (avg_bits - 2), 1 << (avg_bits + 2)
        )

    # self-select the extraction route (bitmask kernel + window reduce,
    # first-hit kernel, or the fused window-first kernel): the
    # serial-chain analysis favors bitmask over first-hit and the fused
    # route saves the mask's HBM round-trip, but the bench should
    # capture the best configuration the chip actually delivers, not a
    # prediction (same policy as the hash kernel calibration; all
    # routes produce identical cuts — tested, and guarded again below)
    if not (os.environ.get("DAT_CDC_ROUTE")
            or "DAT_CDC_FIRST_KERNEL" in os.environ):
        cal = {}
        golden_cuts = None
        # "fused" is pallas-only; off-Pallas it silently aliases bitmask
        # — timing it there would duplicate a leg and could mislabel
        # extract_route in the artifact.  rabin.pallas_active is the one
        # owner of that decision
        routes = (("bitmask", "first", "fused") if rabin.pallas_active()
                  else ("bitmask", "first"))
        # advisor r4: EVERY route is validated against a HOST reference
        # before it may participate — a miscutting route must not win
        # (or disqualify the correct routes) by forfeit.  The reference
        # covers a prefix (a full-slab D2H is not part of what this
        # config times); every cut below prefix_end - 2*max_size is
        # determined by the prefix bytes alone, so that comparison is
        # exact.  Cross-route full-slab equality (the golden check
        # below) covers the remaining 99%+ of the slab: a route that
        # passes the prefix but diverges later is logged WITH the
        # divergence position — not silently dropped — because at that
        # point the prefix can no longer say which side is wrong.
        from dat_replication_protocol_tpu.runtime import native as _nat

        have_native = _nat.available()
        pre_b = min((8 if have_native else 1) << 20, slab_bytes)
        pre = np.frombuffer(
            np.asarray(words[: pre_b // 4]).tobytes(), dtype=np.uint8
        )
        # the reference applies the SAME window thinning as the device
        # routes (begin() passes thin_bits=avg_bits-2): unthinned
        # greedy can legitimately pick a candidate thinning dropped,
        # and every route would then spuriously fail the check
        thn = avg_bits - 2
        ref_cands = (
            _nat.gear_candidates(pre, avg_bits, thn)
            if have_native
            else np.asarray(
                rabin.host_thin(
                    rabin.host_candidates(pre.tobytes(), avg_bits), thn
                ),
                dtype=np.int64,
            )
        )
        ref_cuts = rabin._greedy_select(
            np.asarray(ref_cands, dtype=np.int64),
            pre_b, 1 << (avg_bits - 2), 1 << (avg_bits + 2),
        )
        lim = pre_b - 2 * (1 << (avg_bits + 2))
        want = [c for c in ref_cuts if c < lim]
        for route in routes:
            os.environ["DAT_CDC_ROUTE"] = route
            try:
                cuts0 = finish(begin())  # compile + warm
                got = [c for c in cuts0 if c < lim]
                if got != want:
                    log(f"bench[cdc]: route {route} FAILED host-"
                        f"reference prefix check; excluded")
                    continue
                if golden_cuts is None:
                    golden_cuts = cuts0
                elif cuts0 != golden_cuts:
                    # both passed the host prefix but diverge later in
                    # the slab: exclude this route from selection and
                    # say exactly where, so the artifact's log is
                    # debuggable instead of a silent forfeit
                    div = next(
                        (i for i, (a, b) in enumerate(
                            zip(cuts0, golden_cuts)) if a != b),
                        min(len(cuts0), len(golden_cuts)),
                    )
                    log(f"bench[cdc]: route {route} CUT MISMATCH vs "
                        f"golden beyond the verified prefix (first "
                        f"divergence at cut #{div}: "
                        f"{cuts0[div] if div < len(cuts0) else 'END'} vs "
                        f"{golden_cuts[div] if div < len(golden_cuts) else 'END'}); "
                        f"excluded — neither side host-verified there")
                    continue
                # median of 3, pipelined like the headline loop so
                # route deltas aren't buried under the link RTT AND one
                # congestion spike can't lock the slower route in (the
                # helper also honors BENCH_SERIAL_FENCE, keeping route
                # selection under the same fencing the headline uses)
                cal[route] = statistics.median(
                    _timed_reps_pipelined(begin, finish, 3)
                )
            except Exception as e:
                log(f"bench[cdc]: route {route} failed ({e})")
        if cal:
            pick = min(cal, key=cal.get)
            os.environ["DAT_CDC_ROUTE"] = pick
            log(f"bench[cdc]: route calibration {cal} -> {pick}")
        else:
            os.environ.pop("DAT_CDC_ROUTE", None)

    cuts = finish(begin())  # warmup/compile
    nchunks = len(cuts)
    # depth-2 pipeline: slab N's position D2H rides under slab N+1's scan,
    # the same overlap chunk_stream applies to real multi-slab streams
    t0 = time.perf_counter()
    pending = []
    for _ in range(reps):
        pending.append(begin())
        if len(pending) >= 2:
            finish(pending.pop(0))
    while pending:
        finish(pending.pop(0))
    dt = time.perf_counter() - t0
    total = reps * slab_bytes
    gib_s = total / dt / (1 << 30)
    log(
        f"bench[cdc]: {total / (1 << 30):.1f} GiB in {dt:.3f}s = {gib_s:.2f} GiB/s "
        f"({nchunks} chunks/slab)"
    )

    # kernel-only rate (no extraction/transfer): the gear scan over
    # device-resident tiles, completion fenced by a scalar reduction
    stride = 1 << 17
    T = slab_bytes // stride
    rows = jax.random.bits(
        jax.random.PRNGKey(8), (T, (stride + 256) // 4), dtype=jnp.uint32
    )
    if on_tpu:
        from dat_replication_protocol_tpu.ops.rabin_pallas import (
            gear_candidates_pallas,
        )

        kern = jax.jit(lambda w: jnp.sum(gear_candidates_pallas(w, avg_bits)))
    else:
        kern = jax.jit(lambda w: jnp.sum(rabin.gear_candidates_tiled(w, avg_bits)))
    np.asarray(kern(rows))
    kdts = _timed_reps_pipelined(lambda: kern(rows), np.asarray, reps)
    kernel_gib_s = rows.nbytes / statistics.median(kdts) / (1 << 30)
    log(f"bench[cdc]: kernel-only {kernel_gib_s:.2f} GiB/s")
    return {
        "metric": "cdc_chunking_throughput",
        "value": round(gib_s, 3),
        "unit": "GiB/s",
        "vs_baseline": None,
        "volume_gib": round(total / (1 << 30), 2),
        "reduced_config": total < (10 << 30),
        "full_config": "10 GiB blob (BASELINE config 4)",
        "kernel_only_gib_s": round(kernel_gib_s, 3),
        "kernel_peak_gib_s": round(rows.nbytes / _peak_span(kdts) / (1 << 30), 3),
        "fence": _fence_mode(),
        "extract_route": rabin.effective_route(),
        "chunks_per_slab": nchunks,
    }


# ---------------------------------------------------------------------------
# config 5: Merkle diff of two snapshots (target >= 10M entries/sec)
# ---------------------------------------------------------------------------


def bench_merkle(quick: bool, backend: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dat_replication_protocol_tpu.ops.merkle import (
        diff_root_guided_packed,
        unpack_mask,
    )

    on_tpu = backend == "tpu"
    if quick:
        log2 = 10  # compile time scales with level count on CPU
    else:
        log2 = 20 if on_tpu else 16
    log2 = _env_int("BENCH_MERKLE_LOG2", log2)
    n = 1 << log2

    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    a_hh = jax.random.bits(keys[0], (n, 4), dtype=jnp.uint32)
    a_hl = jax.random.bits(keys[1], (n, 4), dtype=jnp.uint32)
    # b differs from a in ~1% of leaves
    flip = jax.random.bernoulli(keys[2], 0.01, (n, 1))
    b_hh = jnp.where(flip, a_hh ^ 1, a_hh)
    b_hl = a_hl
    jax.block_until_ready((a_hh, a_hl, b_hh, b_hl))

    def dispatch():
        bits, _, _ = diff_root_guided_packed(a_hh, a_hl, b_hh, b_hl)
        return bits

    def fence(bits):
        # honest end-to-end: packed-mask transfer + host bit expansion +
        # index extraction included in every rep
        return np.nonzero(unpack_mask(bits, n))[0]

    idx = fence(dispatch())  # warmup/compile
    reps = 3 if quick else 10
    rep_dts = _timed_reps_pipelined(dispatch, fence, reps)
    dt = sum(rep_dts)
    rate = n / statistics.median(rep_dts)

    # the routed LOCAL diff engine (ops.merkle.diff_snapshots): on a CPU
    # host that is one vectorized compare — the tree walk above stays
    # the headline (it IS config 5's metric), this field shows what a
    # local caller gets from the routing layer
    from dat_replication_protocol_tpu.ops.merkle import diff_snapshots
    from dat_replication_protocol_tpu.utils.routing import prefer_host

    local_rate = None
    if prefer_host("DAT_DEVICE_MERKLE"):
        ah, al = np.asarray(a_hh), np.asarray(a_hl)
        bh, bl = np.asarray(b_hh), np.asarray(b_hl)
        lidx = diff_snapshots(ah, al, bh, bl)  # warm
        ldts = _timed_reps(
            lambda: diff_snapshots(ah, al, bh, bl), 3 if quick else 10
        )
        local_rate = n / statistics.median(ldts)
        assert len(lidx) == len(idx)
        log(f"bench[merkle]: routed local diff {local_rate / 1e6:.1f} "
            f"M entries/s")
    log(
        f"bench[merkle]: {log2}-level diff x{reps} in {dt:.3f}s = "
        f"{rate / 1e6:.2f} M entries/s median ({reps * n / dt / 1e6:.2f} "
        f"aggregate; {len(idx)} differing leaves)"
    )
    # divergent-replica reconciliation rate (round-2 verdict missing #2):
    # two logs differing by inserts/deletes/flips, end-to-end through
    # hashing, key-addressed sketches, and the cell-level tree diff
    from dat_replication_protocol_tpu.ops import reconcile

    # full config-5 snapshot scale by default (round-4 verdict #4: 1M+1M;
    # 1.85M records/s at 200k said nothing about slot-table pressure or
    # bucketing at the scale the config names)
    rrows = _env_int("BENCH_RECONCILE_ROWS", 2_000 if quick else 1_000_000)
    keys_a = [b"row-%07d" % i for i in range(rrows)]
    recs_a = [b"value-of:" + k for k in keys_a]
    keys_b = list(keys_a)
    recs_b = list(recs_a)
    rng = np.random.default_rng(5)
    # positions drawn once against the ORIGINAL length (stable spread of
    # inserts across the log; the insert loop itself is O(k·n) memmove —
    # ~0.7 s at 1M, measured, and untimed setup either way)
    pos = sorted((int(p) for p in rng.integers(0, rrows,
                                               max(1, rrows // 1000))),
                 reverse=True)
    for j, p in enumerate(pos):
        keys_b.insert(p, b"new-%d" % j)
        recs_b.insert(p, b"value-of-new-%d" % j)
    log2_slots = max(8, (rrows * 2).bit_length())
    # warm pass pays the jit compiles (same shapes as the timed pass);
    # the timed pass measures the pipeline, not XLA's cold start
    reconcile.reconcile(
        reconcile.LogSummary(recs_a, keys_a, log2_slots),
        reconcile.LogSummary(recs_b, keys_b, log2_slots),
    )
    t0 = time.perf_counter()
    sa = reconcile.LogSummary(recs_a, keys_a, log2_slots)
    sb = reconcile.LogSummary(recs_b, keys_b, log2_slots)
    out = reconcile.reconcile(sa, sb)
    rdt = time.perf_counter() - t0
    rrate = (len(keys_a) + len(keys_b)) / rdt
    log(
        f"bench[merkle]: reconcile {len(keys_a)}+{len(keys_b)} records in "
        f"{rdt:.3f}s = {rrate / 1e6:.2f} M records/s "
        f"({len(out['slots'])} differing cells)"
    )
    return {
        "metric": "merkle_diff_rate",
        "value": round(rate, 0),
        "unit": "entries/s",
        "vs_baseline": round(rate / 10e6, 4),
        "aggregate_entries_s": round(reps * n / dt, 0),
        "peak_entries_s": round(n / _peak_span(rep_dts), 0),
        "fence": _fence_mode(),
        "leaves": n,
        "reduced_config": n < (1 << 20) or (len(keys_a) + len(keys_b)) < 2_000_000,
        "full_config": "2 x 1M leaves; reconcile 1M+1M records "
                       "(BASELINE config 5)",
        "local_diff_entries_s": round(local_rate, 0) if local_rate else None,
        "reconcile_records_s": round(rrate, 0),
        "reconcile_records": len(keys_a) + len(keys_b),
    }


# ---------------------------------------------------------------------------
# config 6: resume latency — checkpoint export -> reconnect -> first
# re-delivered frame (ROBUSTNESS.md's recovery-cost number)
# ---------------------------------------------------------------------------


def bench_resume(quick: bool, backend: str) -> dict:
    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.session.faults import (
        FaultPlan,
        FaultyReader,
        TransportFault,
        bytes_reader,
    )
    from dat_replication_protocol_tpu.session.reconnect import (
        BackoffPolicy,
        run_resumable,
    )
    from dat_replication_protocol_tpu.session.resume import WireJournal

    rows = _env_int("BENCH_RESUME_ROWS", 2_000 if quick else 20_000)
    reps = _env_int("BENCH_RESUME_REPS", 20 if quick else 100)

    enc = protocol.encode()
    journal = WireJournal()
    enc.attach_journal(journal)
    # fleet-plane cursors (ISSUE 11): with --metrics the config's
    # --fleet-snapshot view carries this link's append/acked offsets
    journal.watermark("bench-resume")
    for i in range(rows):
        enc.change({"key": f"key-{i:07d}", "change": i, "from": i,
                    "to": i + 1, "value": b"v" * (i % 48)})
    enc.finalize()
    while enc.read(1 << 18) is not None:
        pass
    wire = journal.read_from(0)
    drop_at = len(wire) // 2

    lat = []

    def one() -> None:
        dec = protocol.decode()
        times = {}

        class TimedReader(FaultyReader):
            def read(self, n):
                try:
                    return super().read(n)
                except TransportFault:
                    times["fault"] = time.perf_counter()
                    raise

        def on_change_after(c, done):
            if "fault" in times and "redeliver" not in times:
                times["redeliver"] = time.perf_counter()
            done()

        dec.change(on_change_after)

        def source(ckpt, failures):
            plan = FaultPlan(
                seed=failures,
                drop_at=(drop_at - ckpt.wire_offset) if failures == 0 else None,
            )
            return TimedReader(bytes_reader(wire[ckpt.wire_offset:]), plan)

        # base=0: measure the machinery, not the (configurable) backoff
        run_resumable(source, dec,
                      BackoffPolicy(base=0.0, max_retries=2, seed=0),
                      chunk_size=1 << 16, expected_total=len(wire),
                      stall_timeout=30)
        assert dec.finished and dec.changes == rows
        lat.append(times["redeliver"] - times["fault"])

    one()  # correctness gate + warmup
    lat.clear()
    t0 = time.perf_counter()
    for _ in range(reps):
        one()
    dt = time.perf_counter() - t0
    lat_ms = sorted(x * 1e3 for x in lat)
    med = statistics.median(lat_ms)
    log(f"bench[resume]: {reps} faulted sessions ({rows} rows) in {dt:.2f}s; "
        f"fault->first-redelivered-frame median {med:.3f} ms "
        f"(p90 {lat_ms[int(0.9 * (len(lat_ms) - 1))]:.3f} ms)")
    return {
        "metric": "resume_latency",
        "value": round(med, 3),
        "unit": "ms",
        "vs_baseline": None,
        "p90_ms": round(lat_ms[int(0.9 * (len(lat_ms) - 1))], 3),
        "rows": rows,
        "wire_bytes": len(wire),
        "sessions_s": round(reps / dt, 1),
    }


# ---------------------------------------------------------------------------
# config 7: wire-level A/B — per-record Change frames vs columnar
# ChangeBatch frames (rows/s both directions + bytes-on-wire; ISSUE 6)
# ---------------------------------------------------------------------------


def bench_wire_batch(quick: bool, backend: str) -> dict:
    import numpy as np

    from dat_replication_protocol_tpu.runtime import native, replay
    from dat_replication_protocol_tpu.wire.change_codec import Change, \
        encode_change
    from dat_replication_protocol_tpu.wire.framing import TYPE_CHANGE, frame

    rows = _env_int("BENCH_WIRE_BATCH_ROWS", 50_000 if quick else 1_000_000)
    batch_rows = _env_int("BENCH_WIRE_BATCH_SIZE", 65_536)
    # the config-2 replay shape: distinct keys within a block, the block
    # repeated to scale — change logs revisit keys, which is exactly
    # what the batch dictionary monetizes
    block_n = min(rows, 4096)
    recs = [
        Change(
            key=f"key-{i:07d}",
            change=i,
            from_=i,
            to=i + 1,
            value=b"v" * (i % 48),
            subset="s" if i % 3 else None,
        )
        for i in range(block_n)
    ]
    block = b"".join(frame(TYPE_CHANGE, encode_change(c)) for c in recs)
    reps = -(-rows // block_n)
    per_record_wire = block * reps
    total_rows = block_n * reps
    cols, _frames = replay.replay_log(
        np.frombuffer(per_record_wire, np.uint8))

    # A: per-record framing — columnar bulk encoder (the incumbent)
    replay.encode_change_columns(replay._slice_columns(cols, 0, 64))  # warm
    t0 = time.perf_counter()
    a_wire = replay.encode_change_columns(cols)
    a_dt = time.perf_counter() - t0
    assert len(a_wire) == len(per_record_wire)

    # B: ChangeBatch framing — same rows, columnar frames
    replay.encode_batch_frames(replay._slice_columns(cols, 0, 64))  # warm
    t0 = time.perf_counter()
    b_wire = replay.encode_batch_frames(cols, rows_per_batch=batch_rows)
    b_dt = time.perf_counter() - t0

    # B decode: whole-log replay of the batch wire (the e2e replay rate)
    b_buf = np.frombuffer(b_wire, np.uint8)
    t0 = time.perf_counter()
    b_cols, _bf = replay.replay_log(b_buf)
    bd_dt = time.perf_counter() - t0
    assert len(b_cols) == total_rows
    assert b_cols.row(0).to_dict() == cols.row(0).to_dict()
    assert b_cols.row(total_rows - 1).to_dict() == \
        cols.row(total_rows - 1).to_dict()

    # A decode, for the same-shape comparison
    t0 = time.perf_counter()
    a_cols, _af = replay.replay_log(np.frombuffer(per_record_wire, np.uint8))
    ad_dt = time.perf_counter() - t0
    assert len(a_cols) == total_rows

    ratio = len(b_wire) / len(per_record_wire)
    # wire cost plane (ISSUE 20): the batch wire replayed through a lit
    # session decoder — goodput/overhead of the bytes the A/B actually
    # compares, off the board's own ledger
    goodput, overhead = _wirecost_decode_ratios(b_wire)
    log(
        f"bench[wire_batch]: {total_rows} rows — encode "
        f"{total_rows / a_dt:,.0f} rows/s per-record vs "
        f"{total_rows / b_dt:,.0f} rows/s batch ({a_dt / b_dt:.1f}x); "
        f"decode {total_rows / ad_dt:,.0f} vs {total_rows / bd_dt:,.0f} "
        f"rows/s; wire {len(per_record_wire)} -> {len(b_wire)} bytes "
        f"({(1 - ratio) * 100:.1f}% smaller)"
    )
    return {
        "metric": "wire_batch_encode_rate",
        "value": round(total_rows / b_dt, 0),
        "unit": "rows/s",
        "vs_baseline": None,
        "native": native.available(),
        "rows": total_rows,
        "reduced_config": total_rows < 1_000_000,
        "full_config": "1M rows (config-2 shape), 64Ki-row batches",
        "batch_rows_per_frame": batch_rows,
        "per_record_encode_rows_s": round(total_rows / a_dt, 0),
        "per_record_decode_rows_s": round(total_rows / ad_dt, 0),
        "batch_decode_rows_s": round(total_rows / bd_dt, 0),
        "per_record_bytes": len(per_record_wire),
        "batch_bytes": len(b_wire),
        "bytes_ratio": round(ratio, 4),
        "goodput_ratio": goodput,
        "overhead_ratio": overhead,
    }


# ---------------------------------------------------------------------------
# config 8: single-pass content addressing A/B — the fused1p route vs the
# two-pass route, bytes -> digests end to end (ISSUE 7)
# ---------------------------------------------------------------------------


def bench_fused_e2e(quick: bool, backend: str) -> dict:
    import numpy as np

    from dat_replication_protocol_tpu.backend.tpu_backend import (
        _host_hash_batch,
    )
    from dat_replication_protocol_tpu.ops.rabin import chunk_stream
    from dat_replication_protocol_tpu.runtime import native
    from dat_replication_protocol_tpu.runtime.content import content_digests

    mib = _env_int("BENCH_FUSED_MIB", 32 if quick else 256)
    reps = _env_int("BENCH_FUSED_REPS", 2 if quick else 3)
    buf = np.random.default_rng(11).integers(0, 256, mib << 20,
                                             dtype=np.uint8)
    n = buf.nbytes

    # pin the HOST engines for the host-group A/B: on an accelerator-
    # backed box the routing layer would otherwise send both routes to
    # the device pipeline and the host comparison would mislabel what
    # ran.  Restored before the (opt-in) device leg below.
    saved_env = {k: os.environ.get(k)
                 for k in ("DAT_DEVICE_CDC", "DAT_DEVICE_HASH")}
    os.environ["DAT_DEVICE_CDC"] = "0"
    os.environ["DAT_DEVICE_HASH"] = "0"
    try:
        out = _bench_fused_e2e_pinned(quick, buf, n, mib, reps)
    finally:
        # restore even when a correctness gate raises: run_config catches
        # the exception and the rest of the bench (the device leg
        # included) must not silently route to host engines
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return _bench_fused_e2e_device_leg(quick, out)


def _bench_fused_e2e_pinned(quick: bool, buf, n: int, mib: int,
                            reps: int) -> dict:
    from dat_replication_protocol_tpu.backend.tpu_backend import (
        _host_hash_batch,
    )
    from dat_replication_protocol_tpu.ops.rabin import chunk_stream
    from dat_replication_protocol_tpu.runtime import native
    from dat_replication_protocol_tpu.runtime.content import content_digests

    # A: the TWO-PASS route — the incumbent bytes->digests composition a
    # session pays today: the gear scan streams every byte once for the
    # cuts, then every chunk is sliced into a payload object and re-read
    # by the routed host digest engine (exactly what a DigestPipeline
    # submit stream does).  Blob bytes cross memory twice, plus a
    # payload materialization per chunk.
    def two_pass():
        cuts = chunk_stream(buf)
        payloads = [buf[a:b].tobytes()
                    for a, b in zip([0] + cuts[:-1], cuts)]
        return cuts, _host_hash_batch(payloads)

    # B: the FUSED single-pass route — cuts and digests in one sweep
    # (native dat_cdc_hash via content_digests' fused1p routing)
    def fused():
        return content_digests(buf, route="fused1p")

    # correctness gate: both routes must produce identical cuts+digests
    # (the fuzz suite pins this; the bench re-checks the exact shapes it
    # times so an artifact can never record a miscutting win)
    cuts_a, digs_a = two_pass()
    cuts_f, digs_f = fused()
    assert list(cuts_a) == list(cuts_f), "route cut divergence"
    assert all(bytes(digs_f[i]) == digs_a[i] for i in
               range(0, len(cuts_f), max(1, len(cuts_f) // 64)))

    # min-of-reps (best rep) on BOTH sides, with the sides INTERLEAVED
    # A,B,A,B,...: the box is shared, and measuring one whole side then
    # the other lets a steal/scheduling drift spanning one side's reps
    # bias the RATIO — interleaving makes drift hit both sides alike,
    # and the min still discards isolated spikes
    tps, fus, t2s = [], [], []
    for _ in range(reps):
        tps.extend(_timed_reps(lambda: two_pass(), 1))
        fus.extend(_timed_reps(lambda: fused(), 1))
        # diagnostic: the strong two-pass (native extents, no per-chunk
        # payload slicing — the content_digests(route="2p") engine this
        # PR also adds); fusion's margin over IT isolates the
        # single-sweep win from the slicing win
        t2s.extend(_timed_reps(
            lambda: content_digests(buf, route="2p"), 1))
    tp, fu, t2 = min(tps), min(fus), min(t2s)
    fused_gib = n / fu / (1 << 30)
    two_gib = n / tp / (1 << 30)
    ratio = fused_gib / two_gib
    log(f"bench[fused_e2e]: {mib} MiB x{reps} — fused1p {fused_gib:.2f} "
        f"GiB/s vs two-pass {two_gib:.2f} GiB/s ({ratio:.2f}x; "
        f"extents two-pass {n / t2 / (1 << 30):.2f})")

    out = {
        "metric": "fused_e2e_throughput",
        "value": round(fused_gib, 3),
        "unit": "GiB/s",
        "vs_baseline": None,
        "native": native.available(),
        "volume_mib": mib,
        "reps": reps,
        "reduced_config": n < (2 << 30),
        "full_config": "2 GiB bytes->digests, min-of-reps",
        "chunks": len(cuts_f),
        "two_pass_gib_s": round(two_gib, 3),
        "two_pass_extents_gib_s": round(n / t2 / (1 << 30), 3),
        "fused_vs_two_pass": round(ratio, 3),
    }

    return out


def _bench_fused_e2e_device_leg(quick: bool, out: dict) -> dict:
    """The opt-in device-group A/B: the single-residency device pipeline
    vs the two-pass host-repack composition, same A/B discipline.  Runs
    OUTSIDE the host-engine env pin (the routing must be free)."""
    import numpy as np

    from dat_replication_protocol_tpu.ops.rabin import chunk_stream

    reps = _env_int("BENCH_FUSED_REPS", 2 if quick else 3)
    if os.environ.get("BENCH_FUSED_DEVICE") == "1":
        import jax

        from dat_replication_protocol_tpu.batch.feed import hash_extents
        from dat_replication_protocol_tpu.ops.fused_cdc_hash_pallas import (
            content_begin,
        )

        dmib = _env_int("BENCH_FUSED_DEVICE_MIB", 64 if quick else 1024)
        dbuf = np.random.default_rng(12).integers(0, 256, dmib << 20,
                                                  dtype=np.uint8)

        def dev_fused():
            cuts, hh, hl = content_begin(dbuf)()
            np.asarray(hh[:1, :1])  # completion fence
            return cuts

        def dev_two_pass():
            cuts = chunk_stream(dbuf)
            ends = np.asarray(cuts, np.int64)
            offs = np.concatenate([np.zeros(1, np.int64), ends[:-1]])
            hash_extents(dbuf, offs, ends - offs)
            return cuts

        assert list(dev_fused()) == list(dev_two_pass())  # warm + gate
        df = min(_timed_reps(lambda: dev_fused(), reps))
        dt2 = min(_timed_reps(lambda: dev_two_pass(), reps))
        out["device_fused_gib_s"] = round(dbuf.nbytes / df / (1 << 30), 3)
        out["device_two_pass_gib_s"] = round(
            dbuf.nbytes / dt2 / (1 << 30), 3)
        out["device_volume_mib"] = dmib
        out["device_backend"] = jax.default_backend()
        log(f"bench[fused_e2e]: device leg fused "
            f"{out['device_fused_gib_s']} vs two-pass "
            f"{out['device_two_pass_gib_s']} GiB/s "
            f"({jax.default_backend()})")
    return out


# ---------------------------------------------------------------------------
# config 9: multi-session hub soak — N concurrent sessions multiplexed
# onto ONE shared ReplicationHub/DigestPipeline (ISSUE 8).  Headline is
# aggregate decode+digest GiB/s; fairness is min/median per-session
# throughput (weighted-fair batching should hold it near 1.0 — a value
# near 0 means one session starved, the regression the gate watches).
# ---------------------------------------------------------------------------


def bench_hub_soak(quick: bool, backend: str) -> dict:
    import threading

    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.hub import ReplicationHub

    sessions = _env_int("BENCH_HUB_SESSIONS", 8 if quick else 16)
    rows = _env_int("BENCH_HUB_ROWS", 2_048 if quick else 16_384)
    blob_kib = _env_int("BENCH_HUB_BLOB_KIB", 256 if quick else 2_048)
    # BENCH_HUB_MESH=auto|N: shard the cross-session hash batch over
    # the device mesh (the `--hub-mesh` shape); on a host-routed backend
    # the hub keeps the host engine
    mesh = os.environ.get("BENCH_HUB_MESH") or None
    if mesh is not None and mesh != "auto":
        mesh = int(mesh)

    # per-session wires built untimed: a bulk change run (the native
    # bulk decode path) plus one blob, distinct keys per session
    wires = []
    for i in range(sessions):
        e = protocol.encode()
        e.change_many([
            {"key": f"s{i}-{j:06d}", "change": j, "from": j, "to": j + 1,
             "value": b"v" * 64}
            for j in range(rows)
        ])
        b = e.blob(blob_kib << 10)
        b.write(bytes(blob_kib << 10))
        b.end()
        e.finalize()
        parts = []
        while True:
            d = e.read(1 << 20)
            if d is None:
                break
            parts.append(d)
        wires.append(b"".join(parts))
    total_bytes = sum(len(w) for w in wires)

    hub = ReplicationHub(mesh=mesh, linger_s=0.002, window_items=1 << 16,
                         window_bytes=64 << 20, parked_budget=1 << 30,
                         max_sessions=sessions + 1)
    done = [None] * sessions
    start_gate = threading.Event()

    def run_one(i: int) -> None:
        start_gate.wait(30)
        t0 = time.perf_counter()
        s = hub.register(f"s{i}")
        dec = protocol.decode(backend="tpu", pipeline=s)
        n = {"d": 0}
        dec.on_digest(lambda kind, seq, d: n.__setitem__("d", n["d"] + 1))
        wire = wires[i]
        step = 1 << 18
        for off in range(0, len(wire), step):
            dec.write(wire[off:off + step])
        dec.end()
        assert dec.finished
        s.close()
        done[i] = (time.perf_counter() - t0, n["d"])

    threads = [threading.Thread(target=run_one, args=(i,), daemon=True)
               for i in range(sessions)]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    start_gate.set()
    for t in threads:
        t.join(600)
    wall = time.perf_counter() - t0
    hub.close()
    assert all(d is not None for d in done), "hub soak session hung"
    digests = sum(d[1] for d in done)
    assert digests == sessions * (rows + 1)

    per_tput = [len(wires[i]) / done[i][0] for i in range(sessions)]
    ordered = sorted(per_tput)
    median = ordered[sessions // 2]
    fairness = (ordered[0] / median) if median > 0 else 0.0
    agg = total_bytes / wall / (1 << 30)
    log(f"bench[hub_soak]: {sessions} sessions x ({rows} rows + "
        f"{blob_kib} KiB blob) — aggregate {agg:.3f} GiB/s, fairness "
        f"min/median {fairness:.2f}, {digests} digests")
    return {
        "metric": "hub_soak_aggregate_throughput",
        "value": round(agg, 3),
        "unit": "GiB/s",
        "vs_baseline": None,
        "sessions": sessions,
        "rows_per_session": rows,
        "blob_kib": blob_kib,
        "total_mib": round(total_bytes / (1 << 20), 1),
        "digests": digests,
        "fairness_min_median": round(fairness, 3),
        "session_gib_s_min": round(ordered[0] / (1 << 30), 4),
        "session_gib_s_median": round(median / (1 << 30), 4),
        "mesh": mesh,
        "reduced_config": sessions < 16 or rows < 16_384,
        "full_config": "16 sessions x (16384 rows + 2 MiB blob) on one "
                       "shared hub",
    }


def bench_fanout(quick: bool, backend: str) -> dict:
    """Config 10 (ISSUE 9): one-to-many fan-out — hash once, serve N.

    One wire session is decoded (digested) EXACTLY ONCE while N
    downstream peers receive its bytes through the BroadcastLog /
    FanoutServer windowed scatter-gather path.  Three proofs in one
    artifact:

    * **peers x MiB/s matrix** — aggregate delivered throughput must
      SCALE with peer count (per-peer marginal cost is a windowed
      writev of already-framed bytes, not a re-hash + re-copy);
    * **hash-once** — the digest-work byte counters
      (device.native.hash.bytes / device.submit.bytes / device.h2d.
      bytes) stay CONSTANT as peers grow (``hash_ratio`` ~ 1.0);
    * **stall isolation** — one peer stalled for ``stall_s`` seconds
      mid-wire leaves the other peers' p99 append->delivery frame
      latency flat (``stalled_arm_p99_ms``), budget-gated.

    Peers are accounting-only sinks (accept-everything, zero copies) —
    the library-level fan-out capacity; the fd/writev kernel path is
    exercised by the unit/chaos suites and the sidecar.
    """
    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.fanout import FanoutServer
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    rows = _env_int("BENCH_FANOUT_ROWS", 2_048 if quick else 16_384)
    blob_kib = _env_int("BENCH_FANOUT_BLOB_KIB", 256 if quick else 2_048)
    peer_counts = [
        int(x) for x in os.environ.get(
            "BENCH_FANOUT_PEERS",
            "1,8,32" if quick else "1,8,64,256").split(",") if x.strip()
    ]
    stall_s = float(os.environ.get("BENCH_FANOUT_STALL_S",
                                   "0.5" if quick else "3.0"))

    # the source wire, built untimed
    e = protocol.encode()
    e.change_many([
        {"key": f"f-{j:06d}", "change": j, "from": j, "to": j + 1,
         "value": b"v" * 64}
        for j in range(rows)
    ])
    b = e.blob(blob_kib << 10)
    b.write(bytes(blob_kib << 10))
    b.end()
    e.finalize()
    parts = []
    while True:
        d = e.read(1 << 20)
        if d is None:
            break
        parts.append(d)
    wire = b"".join(parts)
    step = 1 << 18

    # the hash-once proof reads obs counters: enable telemetry for this
    # config (conftest-style save/restore; the overhead rides both
    # sides of the matrix equally)
    _DIGEST_COUNTERS = ("device.native.hash.bytes", "device.submit.bytes",
                        "device.h2d.bytes")

    def _digest_work() -> int:
        snap = obs_metrics.snapshot()["counters"]
        return sum(int(snap.get(k, 0)) for k in _DIGEST_COUNTERS)

    def _count_sink():
        # accounting-only consumer: accepts every view, copies nothing
        return lambda views: sum(len(v) for v in views)

    was_on = obs_metrics.OBS.on
    obs_metrics.enable()
    from dat_replication_protocol_tpu.obs.wirecost import WIRECOST
    WIRECOST.reset_for_tests()
    try:
        matrix: dict = {}
        p99_by_n: dict = {}
        hash_by_n: dict = {}
        for n in peer_counts:
            srv = FanoutServer(retention_budget=len(wire) + (1 << 20),
                               stall_timeout=60.0)
            try:
                peers = [srv.attach_peer(f"p{i}", sink=_count_sink())
                         for i in range(n)]
                dec = protocol.decode(backend="tpu")
                ndig = {"d": 0}
                dec.on_digest(
                    lambda kind, seq, d: ndig.__setitem__("d",
                                                          ndig["d"] + 1))
                h0 = _digest_work()
                t0 = time.perf_counter()
                for off in range(0, len(wire), step):
                    chunk = wire[off:off + step]
                    srv.publish(chunk)   # fan-out: bytes only
                    dec.write(chunk)     # digest work: exactly once
                dec.end()
                srv.seal()
                assert srv.drain(120), "fan-out drain hung"
                wall = time.perf_counter() - t0
                hash_by_n[str(n)] = _digest_work() - h0
                assert dec.finished and ndig["d"] == rows + 1
                stats = [p.stats() for p in peers]
                assert all(st["done"] and st["sent_bytes"] == len(wire)
                           for st in stats)
                matrix[str(n)] = round(
                    n * len(wire) / wall / (1 << 20), 1)
                p99s = [st["lat_p99_ms"] for st in stats
                        if st["lat_p99_ms"] is not None]
                p99_by_n[str(n)] = max(p99s) if p99s else None
            finally:
                srv.close()
            log(f"bench[fanout]: {n} peers — {matrix[str(n)]} MiB/s "
                f"aggregate, p99 {p99_by_n[str(n)]} ms, digest-work "
                f"{hash_by_n[str(n)]} bytes")

        hash_vals = [v for v in hash_by_n.values() if v > 0]
        hash_ratio = (round(max(hash_vals) / min(hash_vals), 4)
                      if hash_vals else None)
        # wire cost plane (ISSUE 20): the matrix ran lit, so the board's
        # session ledger already holds the digest leg's wire — read the
        # goodput/overhead watermarks straight off it
        goodput, overhead = _wirecost_ratios("session")

        # stalled-peer arm: one of 8 peers stops accepting for stall_s
        # seconds at the half-way byte (below the shed timeout — it
        # lags, bounded by its window, and must not move the others'
        # p99)
        n_stall = 8
        srv = FanoutServer(retention_budget=len(wire) + (1 << 20),
                           stall_timeout=max(60.0, stall_s * 4))
        try:
            gate = {"t": None}
            stalled_got = {"n": 0}

            def stall_sink(views):
                if gate["t"] is None:
                    gate["t"] = time.perf_counter() + stall_s
                if time.perf_counter() < gate["t"]:
                    budget = len(wire) // 2 - stalled_got["n"]
                    if budget <= 0:
                        return 0
                else:
                    budget = 1 << 60
                take = 0
                for v in views:
                    take += min(len(v), budget - take)
                    if take >= budget:
                        break
                stalled_got["n"] += take
                return take

            staller = srv.attach_peer("staller", sink=stall_sink)
            healthy = [srv.attach_peer(f"h{i}", sink=_count_sink())
                       for i in range(n_stall - 1)]
            for off in range(0, len(wire), step):
                srv.publish(wire[off:off + step])
            srv.seal()
            assert srv.drain(120 + stall_s), "stalled arm drain hung"
            h_stats = [p.stats() for p in healthy]
            assert all(st["done"] and st["sent_bytes"] == len(wire)
                       for st in h_stats)
            st_stall = staller.stats()
            assert st_stall["done"] and st_stall["shed"] is None
            stalled_p99 = max(st["lat_p99_ms"] for st in h_stats
                              if st["lat_p99_ms"] is not None)
        finally:
            srv.close()
        log(f"bench[fanout]: stalled arm ({stall_s}s) — healthy p99 "
            f"{stalled_p99} ms")
    finally:
        WIRECOST.reset_for_tests()
        obs_metrics.OBS.on = was_on

    top = str(max(peer_counts))
    return {
        "metric": "fanout_aggregate_delivered_throughput",
        "value": matrix[top],
        "unit": "MiB/s",
        "vs_baseline": None,
        "peers": int(top),
        "wire_mib": round(len(wire) / (1 << 20), 2),
        "rows": rows,
        "blob_kib": blob_kib,
        "peers_mib_s": matrix,
        "p99_ms": p99_by_n,
        "digest_work_bytes": hash_by_n,
        "hash_ratio": hash_ratio,
        "stall_s": stall_s,
        "stalled_arm_p99_ms": stalled_p99,
        "goodput_ratio": goodput,
        "overhead_ratio": overhead,
        "reduced_config": rows < 16_384 or int(top) < 256,
        "full_config": "1/8/64/256 peers x (16384 rows + 2 MiB blob), "
                       "3 s stalled-peer arm",
    }


# ---------------------------------------------------------------------------
# config 11: rateless coded-symbol reconciliation A/B — wire bytes and
# wall-clock vs the sketch-table exchange and the tree-guided descent
# at k ∈ {10, 1000, 100000} on 1M+1M divergent replicas (ISSUE 10)
# ---------------------------------------------------------------------------


def bench_reconcile_rateless(quick: bool, backend: str) -> dict:
    """Config 11 (ISSUE 10): three anti-entropy protocols reconciling
    the same two divergent change logs (n records each, symmetric
    difference k), each billed its REAL wire bytes and wall clock:

    * **rateless** (the new path): coded-symbol stream + peeling decode
      + ChangeBatch record exchange (`runtime/reconcile_driver.py`) —
      O(k) wire, no estimate of k;
    * **sketch** (the incumbent): `ops/reconcile.LogSummary` tables
      exchanged whole (O(nslots) wire) + differing-slot record exchange
      (collision overhead included);
    * **tree** (the remote refinement): the same sketch tables walked
      via the `tree_sync` descent (O(diff · log n) wire in log n round
      trips) — levels folded on the HOST engine so this config never
      initializes a device backend (`import jax` alone is the descent
      helper's only jax exposure).

    The acceptance claims ride the MIDDLE k arm (k=1000 at full
    config): rateless wire <= 5% of the sketch exchange, and rateless
    end-to-end wall-clock beats the sketch path.  The k=100000 arm
    documents the crossover honestly — when the diff stops being
    small, the O(n) table pass wins wall-clock while rateless still
    wins wire.
    """
    import numpy as np

    from dat_replication_protocol_tpu.ops import reconcile
    from dat_replication_protocol_tpu.runtime import native, replay
    from dat_replication_protocol_tpu.runtime.reconcile_driver import (
        RatelessReplica,
        _batch_wire_len,
        _select_rows,
        reconcile_local,
    )
    from dat_replication_protocol_tpu.runtime.tree_sync import (
        TreeSyncSession,
        sync,
    )

    n = _env_int("BENCH_RECONCILE_N", 20_000 if quick else 1_000_000)
    ks = [int(x) for x in os.environ.get(
        "BENCH_RECONCILE_KS",
        "10,100" if quick else "10,1000,100000").split(",") if x.strip()]
    ks = [k for k in ks if 2 <= k <= n // 2]
    kmax = max(ks)

    # synthetic change log, columnar from the start (no per-record
    # Python): fixed-width keys/values, every record unique.  A is rows
    # [0, n); the k-arm's B is rows [k//2, n + k - k//2) — k//2 records
    # only in A, k - k//2 only in B, everything else shared.
    total = n + (kmax - kmax // 2)
    key_w, val_w = 10, 16
    key_heap = b"".join(b"r-%08d" % i for i in range(total))
    val_heap = b"".join(b"value-of-%07x" % (i & 0xFFFFFFF)
                        for i in range(total))
    assert len(val_heap) == val_w * total
    buf = np.frombuffer(key_heap + val_heap, np.uint8)
    ar = np.arange(total, dtype=np.int64)
    cols = replay.ChangeColumns(
        buf=buf,
        change=(ar & 0xFFFFFFFF).astype(np.uint32),
        from_=(ar & 0xFFFFFFFF).astype(np.uint32),
        to=((ar + 1) & 0xFFFFFFFF).astype(np.uint32),
        key_off=ar * key_w,
        key_len=np.full(total, key_w, np.int64),
        sub_off=np.zeros(total, np.int64),
        sub_len=np.full(total, -1, np.int64),
        val_off=len(key_heap) + ar * val_w,
        val_len=np.full(total, val_w, np.int64),
    )
    # sketch-path inputs, materialized untimed (its API takes lists):
    # canonical payload bytes + key bytes per record
    payloads = replay.canonical_change_payloads(cols)
    keys_list = [key_heap[i * key_w:(i + 1) * key_w]
                 for i in range(total)]
    log2_slots = max(8, (n * 2).bit_length())
    nslots = 1 << log2_slots

    def _table_levels(table):
        """Host-engine merkle levels over sketch-table cells (cells are
        digest-shaped; ops/reconcile.table_leaves' layout in numpy)."""
        level = np.ascontiguousarray(table).view(np.uint8).reshape(-1, 32)
        raws = [level]
        while len(level) > 1:
            half = len(level) // 2
            offs = np.arange(half, dtype=np.int64) * 64
            lens = np.full(half, 64, np.int64)
            level = native.hash_many_fallback(level.reshape(-1), offs, lens)
            raws.append(level)
        hh, hl = [], []
        for raw in raws:
            w = raw.view("<u4").reshape(-1, 8)
            hl.append(np.ascontiguousarray(w[:, 0::2]))
            hh.append(np.ascontiguousarray(w[:, 1::2]))
        return hh, hl

    arms = {}
    for k in ks:
        ka, kb = k // 2, k - k // 2
        a_cols = replay._slice_columns(cols, 0, n)
        b_cols = replay._slice_columns(cols, ka, n + kb)

        # --- rateless: digests + symbol stream + peel + records, e2e
        t0 = time.perf_counter()
        out = reconcile_local(RatelessReplica(a_cols),
                              RatelessReplica(b_cols))
        rl_wall = time.perf_counter() - t0
        assert len(out["a_rows"]) == ka and len(out["b_rows"]) == kb
        rl_wire = out["wire_bytes"]

        # --- sketch: summaries + whole-table exchange + slot bucketing
        t0 = time.perf_counter()
        sa = reconcile.LogSummary(payloads[:n], keys_list[:n], log2_slots)
        sb = reconcile.LogSummary(payloads[ka:n + kb],
                                  keys_list[ka:n + kb], log2_slots)
        sum_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        slots = reconcile.diff_sketches(sa.table, sb.table)
        rows_a = np.nonzero(np.isin(sa.slots, slots))[0]
        rows_b = np.nonzero(np.isin(sb.slots, slots))[0]
        rec_wire = (_batch_wire_len(_select_rows(a_cols, rows_a))
                    + _batch_wire_len(_select_rows(b_cols, rows_b)))
        sk_wall = sum_wall + time.perf_counter() - t0
        sk_wire = nslots * 32 + len(slots) * 8 + rec_wire

        # --- tree-guided descent over the same tables (reuses the
        # summaries: its e2e wall = summary build + levels + descent)
        t0 = time.perf_counter()
        ta = TreeSyncSession(*_table_levels(sa.table))
        tb = TreeSyncSession(*_table_levels(sb.table))
        transcript = []
        tslots = sync(ta, tb, transcript)
        rows_a = np.nonzero(np.isin(sa.slots, tslots))[0]
        rows_b = np.nonzero(np.isin(sb.slots, tslots))[0]
        tr_rec = (_batch_wire_len(_select_rows(a_cols, rows_a))
                  + _batch_wire_len(_select_rows(b_cols, rows_b)))
        tr_wall = sum_wall + time.perf_counter() - t0
        tr_wire = sum(nb for _, nb in transcript) + tr_rec
        assert sorted(tslots) == sorted(slots.tolist())

        arms[str(k)] = {
            "rateless_wall_s": round(rl_wall, 3),
            "rateless_wire": rl_wire,
            "rateless_symbols": out["symbols"],
            "rateless_rounds": out["rounds"],
            "sketch_wall_s": round(sk_wall, 3),
            "sketch_wire": sk_wire,
            "tree_wall_s": round(tr_wall, 3),
            "tree_wire": tr_wire,
            "wire_ratio_vs_sketch": round(rl_wire / sk_wire, 5),
            "speedup_vs_sketch": round(sk_wall / rl_wall, 3),
        }
        log(f"bench[reconcile_rateless]: k={k} — rateless "
            f"{rl_wire} B / {rl_wall:.2f}s ({out['symbols']} symbols, "
            f"{out['rounds']} rounds) vs sketch {sk_wire} B / "
            f"{sk_wall:.2f}s vs tree {tr_wire} B / {tr_wall:.2f}s")

    # wire cost plane leg (ISSUE 20): one LIT two-replica exchange at a
    # scaled shape (same fixed-width key/value records) prices the
    # reconcile wire's framing overhead on the board's own ledger —
    # symbols + repair batches per direction, transport-tiled
    from dat_replication_protocol_tpu.cluster import (
        ReplicaNode,
        gossip_exchange,
    )
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics
    from dat_replication_protocol_tpu.obs.wirecost import WIRECOST

    n_cost = min(n, 4096)
    k_cost = max(2, min(128, n_cost // 8))
    ka_c, kb_c = k_cost // 2, k_cost - k_cost // 2

    def _cost_recs(lo: int, hi: int) -> list:
        return [{"key": "r-%08d" % i, "change": i, "from": i, "to": i + 1,
                 "value": b"value-of-%07x" % (i & 0xFFFFFFF)}
                for i in range(lo, hi)]

    was_on = obs_metrics.OBS.on
    obs_metrics.enable()
    WIRECOST.reset_for_tests()
    try:
        ra = ReplicaNode("a", _cost_recs(0, n_cost))
        rb = ReplicaNode("b", _cost_recs(ka_c, n_cost + kb_c))
        gossip_exchange(ra, rb)
        goodput, overhead = _wirecost_ratios()
    finally:
        WIRECOST.reset_for_tests()
        obs_metrics.OBS.on = was_on

    mid = str(ks[min(1, len(ks) - 1)])
    m = arms[mid]
    return {
        "metric": "reconcile_rateless_rate",
        "value": round(2 * n / m["rateless_wall_s"], 0),
        "unit": "records/s",
        "vs_baseline": None,
        "native": native.available(),
        "n": n,
        "ks": ks,
        "mid_k": int(mid),
        "arms": arms,
        "wire_ratio_mid": m["wire_ratio_vs_sketch"],
        "speedup_vs_sketch_mid": m["speedup_vs_sketch"],
        "goodput_ratio": goodput,
        "overhead_ratio": overhead,
        "reduced_config": n < 1_000_000,
        "full_config": "1M+1M replicas, k in {10, 1000, 100000}",
    }


# ---------------------------------------------------------------------------
# config 12: content-addressed snapshot bootstrap — stale-joiner wire
# scales with staleness, a cold flash crowd shares one hash pass, and
# mid-snapshot resume is exactly-once (ISSUE 12)
# ---------------------------------------------------------------------------


def bench_snapshot_bootstrap(quick: bool, backend: str) -> dict:
    """Config 12 (ISSUE 12): the snapshot bootstrap's three claims,
    each measured on the real protocol with exact wire metering:

    * **stale arm** — a joiner whose dataset diverges in ~2% of its
      CDC chunks reconciles its chunk set (weighted rateless symbols)
      and moves <= 5% of the cold full-transfer bytes: bytes-on-wire
      scale with STALENESS, not dataset size;
    * **cold flash crowd** — N joiners bootstrap the same manifest and
      the source's digest-work counters stay flat (``hash_ratio`` 1.0):
      the dataset is hashed once at materialize, the shared cold log is
      framed once, every session is served zero-copy slices;
    * **chaos arm** — a recorded joiner wire is torn mid-CHUNKS-frame
      and resumed through the reconnect driver: the assembled dataset
      is byte-exact and every chunk verified EXACTLY once.

    Host-group: the protocol core is numpy + native.
    """
    import numpy as np

    from dat_replication_protocol_tpu.obs import metrics as obs_metrics
    from dat_replication_protocol_tpu.runtime.snapshot_driver import (
        SnapshotSource,
        snapshot_local,
    )

    mib = _env_int("BENCH_SNAPSHOT_MIB", 8 if quick else 1024)
    joiners = _env_int("BENCH_SNAPSHOT_JOINERS", 8)
    stale_frac = float(os.environ.get("BENCH_SNAPSHOT_STALE", "0.02"))

    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, mib << 20, dtype=np.uint8)

    # SOURCE digest-work counters: dataset bytes through the fused
    # chunk-hash pass (host route) or shipped to the device (device
    # route).  device.native.hash.bytes is deliberately excluded — the
    # joiners' own merkle-root verification (32 B/chunk, per session BY
    # DESIGN) rides it and would read as false source work.
    _DIGEST_COUNTERS = ("cdc.fused.bytes",
                        "device.submit.bytes", "device.h2d.bytes")

    def _digest_work() -> int:
        snap = obs_metrics.snapshot()["counters"]
        return sum(int(snap.get(k, 0)) for k in _DIGEST_COUNTERS)

    was_on = obs_metrics.OBS.on
    obs_metrics.enable()
    try:
        h0 = _digest_work()
        t0 = time.perf_counter()
        src = SnapshotSource(data)  # ONE hash+read pass, counted
        mat_wall = time.perf_counter() - t0
        hash_once = _digest_work() - h0

        # -- stale arm: 2% of chunks diverge ------------------------------
        n_chunks = len(src.offs)
        pick = rng.choice(n_chunks, size=max(1, int(n_chunks * stale_frac)),
                          replace=False)
        stale = data.copy()
        stale[src.offs[pick]] ^= 0x5A
        t0 = time.perf_counter()
        out = snapshot_local(src, stale.tobytes())
        stale_wall = time.perf_counter() - t0
        assert out["data"] == data.tobytes()
        stale_wire = out["wire_bytes"]
        del stale

        # -- cold flash crowd: N joiners, one hash pass --------------------
        h1 = _digest_work()
        t0 = time.perf_counter()
        cold_wire = None
        for _ in range(joiners):
            cold = snapshot_local(src, None)
            assert cold["data"] == data.tobytes()
            cold_wire = cold["wire_bytes"]
        crowd_wall = time.perf_counter() - t0
        crowd_hash = _digest_work() - h1
        hash_ratio = (hash_once + crowd_hash) / max(1, hash_once)

        # -- chaos arm: torn mid-chunk, resumed exactly-once ---------------
        # the arm records a REAL session snapshot wire with the plane
        # already lit: reset the board first so its tx ledger holds
        # exactly that wire's goodput/overhead (ISSUE 20)
        from dat_replication_protocol_tpu.obs.wirecost import WIRECOST
        WIRECOST.reset_for_tests()
        chaos = _snapshot_chaos_arm(src, data)
        goodput, overhead = _wirecost_ratios("session")
        WIRECOST.reset_for_tests()
    finally:
        obs_metrics.OBS.on = was_on

    ratio = stale_wire / max(1, cold_wire)
    log(f"bench[snapshot_bootstrap]: {mib} MiB, {n_chunks} chunks — "
        f"stale({stale_frac:.0%}) {stale_wire} B vs cold {cold_wire} B "
        f"(ratio {ratio:.4f}); crowd x{joiners} hash_ratio "
        f"{hash_ratio:.3f}; chaos {chaos}")
    return {
        "metric": "snapshot_bootstrap_stale_wire_ratio",
        "value": round(ratio, 5),
        "unit": "ratio",
        "vs_baseline": None,
        "dataset_mib": mib,
        "chunks": n_chunks,
        "stale_frac": stale_frac,
        "stale_wire_bytes": stale_wire,
        "cold_wire_bytes": cold_wire,
        "stale_wall_s": round(stale_wall, 3),
        "materialize_wall_s": round(mat_wall, 3),
        "chunks_reused": out["chunks_reused"],
        "symbols": out["symbols"],
        "joiners": joiners,
        "crowd_wall_s": round(crowd_wall, 3),
        "crowd_mib_s": round(joiners * mib / max(crowd_wall, 1e-9), 1),
        "hash_once_bytes": hash_once,
        "crowd_hash_bytes": crowd_hash,
        "hash_ratio": round(hash_ratio, 4),
        "chaos": chaos,
        "goodput_ratio": goodput,
        "overhead_ratio": overhead,
        "reduced_config": mib < 1024,
        "full_config": "1 GiB dataset, 2% stale chunks, 8-joiner cold "
                       "crowd, torn-wire resume",
    }


def _snapshot_chaos_arm(src, data) -> dict:
    """Record one stale-joiner wire, tear it inside the first CHUNKS
    frame, resume through the reconnect driver, and prove exactly-once:
    byte-exact assembly, every wanted chunk verified once."""
    import numpy as np

    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.runtime.snapshot_driver import (
        SnapshotJoiner,
        SnapshotResponder,
    )
    from dat_replication_protocol_tpu.session.faults import (
        FaultPlan,
        FaultyReader,
        bytes_reader,
    )
    from dat_replication_protocol_tpu.session.reconnect import (
        BackoffPolicy,
        run_resumable,
    )
    from dat_replication_protocol_tpu.session.resume import WireJournal
    from dat_replication_protocol_tpu.wire import snapshot_codec as sn
    from dat_replication_protocol_tpu.wire.framing import (
        CAP_SNAPSHOT,
        iter_frames,
    )

    # the chaos dataset is a small window of the bench dataset: the
    # exactly-once contract is size-independent and the recorded wire
    # replays byte-at-a-time territory
    chaos_data = np.ascontiguousarray(data[: 4 << 20])
    from dat_replication_protocol_tpu.runtime.snapshot_driver import (
        SnapshotSource,
    )

    csrc = SnapshotSource(chaos_data)
    stale = chaos_data.copy()
    stale[csrc.offs[:: max(1, len(csrc.offs) // 20)]] ^= 0x5A
    resp = SnapshotResponder(csrc)
    pilot = SnapshotJoiner(stale.tobytes())
    e = protocol.encode(peer_caps=CAP_SNAPSHOT)
    j = WireJournal()
    e.attach_journal(j)
    pending = list(resp.begin_payloads())
    while pending and not pilot.done:
        replies = []
        for payload in pending:
            e.snapshot_frame(payload)
            replies.extend(pilot.handle(sn.decode_snapshot(payload)))
        pending = []
        for r in replies:
            pending.extend(resp.handle(sn.decode_snapshot(r)))
    e.finalize()
    while e.read(4096) is not None:
        pass
    wanted = pilot.chunks_verified
    wire = j.read_from(0)

    # first CHUNKS frame extent -> truncate mid-body
    cut = None
    for _start, _tid, p0, end in iter_frames(wire):
        if wire[p0] == sn.SN_CHUNKS:
            cut = p0 + (end - p0) // 2  # mid-body
            break
    assert cut is not None

    joiner = SnapshotJoiner(stale.tobytes())
    dec = protocol.decode()
    dec.snapshot(lambda msg, done: (joiner.handle(msg), done()))

    def source(ckpt, failures):
        remaining = wire[ckpt.wire_offset:]
        plan = FaultPlan(truncate_at=cut) if failures == 0 else FaultPlan()
        return FaultyReader(bytes_reader(remaining), plan)

    stats = run_resumable(
        source, dec, BackoffPolicy(base=0.0005, cap=0.005, max_retries=4),
        expected_total=len(wire))
    out = joiner.result()
    return {
        "resumed": stats["reconnects"] >= 1,
        "exactly_once": (out["data"] == chaos_data.tobytes()
                         and joiner.chunks_verified == wanted),
        "reconnects": stats["reconnects"],
        "chunks_verified": joiner.chunks_verified,
        "wanted": wanted,
    }


# ---------------------------------------------------------------------------
# config 13: kernel-bypass wire pump (ISSUE 14, ROADMAP item 5)
# ---------------------------------------------------------------------------


def bench_wire_pump(quick: bool, backend: str) -> dict:
    """Config 13: the batched-syscall transport pump A/B (host group).

    Three proofs in one config, all over REAL kernel sockets:

    * **e2e bytes->digest A/B** — one digest session (the sidecar
      shape: TpuDecoder, no per-row handler) pumped through a
      socketpair, native pump vs the Python reference pump, sides
      interleaved + max-of-reps.  ``value`` (and ``e2e_host_gib_s``)
      is the native route; ``pump_ratio`` the A/B.
    * **hub aggregate vs session count** — N concurrent sessions, each
      its own socketpair + native pump feeding one shared
      ReplicationHub; ``hub_agg_gib_s`` per count and
      ``hub_scaling`` = agg(max)/agg(1), the GIL-flatness probe (a
      GIL-bound wire path pins this at ~1.0 regardless of cores).
    * **syscall economics** — ``syscalls_saved``/``pump_batches`` from
      the ``transport.pump.*`` counters (requires ``--metrics``;
      ``None`` otherwise): messages landed minus kernel entries paid.
    """
    import socket
    import threading

    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.hub import ReplicationHub
    from dat_replication_protocol_tpu.session import pump as spump

    mib = _env_int("BENCH_PUMP_MIB", 16 if quick else 64)
    reps = _env_int("BENCH_PUMP_REPS", 2 if quick else 3)
    counts = [int(x) for x in os.environ.get(
        "BENCH_PUMP_SESSIONS", "1,4" if quick else "1,4,16").split(",")]

    def build_wire(total_mib: int, seed: int = 0) -> bytes:
        # the sidecar session shape at wire-bound proportions: a bulk
        # change run (the columnar bulk-decode path, ~1.5% of bytes —
        # more and the PER-ROW digest submits dominate the measurement,
        # hiding the wire path this config exists to price) + 1 MiB
        # blobs (the extent path) for the volume
        rows = (total_mib << 20) // 64 // 89  # ~89 wire bytes per row
        e = protocol.encode()
        e.change_many([
            {"key": f"s{seed}-{j:07d}", "change": j, "from": j,
             "to": j + 1, "value": b"v" * 64}
            for j in range(rows)
        ])
        for _ in range(max(1, total_mib - (total_mib // 64))):
            b = e.blob(1 << 20)
            b.write(bytes(1 << 20))
            b.end()
        e.finalize()
        parts = []
        while True:
            d = e.read(1 << 20)
            if d is None:
                break
            parts.append(d)
        return b"".join(parts)

    def run_session_over_socket(wire: bytes, pipeline=None) -> float:
        """One digest session pumped through a socketpair on the
        CURRENT route; returns seconds."""
        a, b = socket.socketpair()
        try:
            # deployment-shaped kernel buffers (1 MiB): the default
            # ~208 KiB socketpair buffer caps what one batched receive
            # can drain — both routes get the same window (fair A/B)
            a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 20)
            b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 20)
            dec = (protocol.decode(backend="tpu", pipeline=pipeline)
                   if pipeline is not None
                   else protocol.decode(backend="tpu"))
            seen = {"d": 0}
            dec.on_digest(
                lambda k, s, d: seen.__setitem__("d", seen["d"] + 1))
            dec.blob(lambda blob, done: (blob.on_data(lambda _c: None),
                                         blob.on_end(done)))

            def feed() -> None:
                mv = memoryview(wire)
                while mv:
                    sent = a.send(mv[:1 << 20])
                    mv = mv[sent:]
                a.shutdown(socket.SHUT_WR)

            t = threading.Thread(target=feed, daemon=True)
            t.start()
            t0 = time.perf_counter()
            spump.recv_pump(dec, b.fileno())
            dt = time.perf_counter() - t0
            t.join(60)
            assert dec.finished and seen["d"] > 0, "pump session failed"
            return dt
        finally:
            a.close()
            b.close()

    wire = build_wire(mib)
    gib = len(wire) / (1 << 30)

    # A/B interleaved (the config-8 doctrine): route env flipped per
    # side, max-of-reps per side so a scheduler hiccup on the shared
    # box cannot misprice either pump
    best = {"native": 0.0, "python": 0.0}
    prev_route = os.environ.get("DAT_PUMP")
    try:
        for _ in range(reps):
            for route in ("python", "native"):
                os.environ["DAT_PUMP"] = route
                dt = run_session_over_socket(wire)
                best[route] = max(best[route], gib / dt)

        # hub aggregate vs session count, native route (each session:
        # its own socketpair + pump thread into the SHARED hub)
        os.environ["DAT_PUMP"] = "native"
        sess_mib = max(4, mib // 8)
        hub_agg: dict = {}
        for n_sessions in counts:
            wires = [build_wire(sess_mib, seed=i + 1)
                     for i in range(n_sessions)]
            hub = ReplicationHub(linger_s=0.002, window_items=1 << 16,
                                 window_bytes=64 << 20,
                                 parked_budget=1 << 30,
                                 max_sessions=n_sessions + 1)
            done = [None] * n_sessions
            gate = threading.Event()

            def run_one(i: int) -> None:
                gate.wait(30)
                s = hub.register(f"p{i}")
                try:
                    done[i] = run_session_over_socket(wires[i],
                                                      pipeline=s)
                finally:
                    s.close()

            threads = [threading.Thread(target=run_one, args=(i,),
                                        daemon=True)
                       for i in range(n_sessions)]
            for t in threads:
                t.start()
            t0 = time.perf_counter()
            gate.set()
            for t in threads:
                t.join(300)
            wall = time.perf_counter() - t0
            hub.close()
            assert all(d is not None for d in done), "hub pump arm hung"
            total = sum(len(w) for w in wires)
            hub_agg[str(n_sessions)] = round(total / wall / (1 << 30), 4)
    finally:
        if prev_route is None:
            os.environ.pop("DAT_PUMP", None)
        else:
            os.environ["DAT_PUMP"] = prev_route

    # syscall economics, when the registry is live (--metrics)
    saved = batches = None
    if _METRICS["on"]:
        from dat_replication_protocol_tpu.obs import metrics as obs_metrics

        counters = obs_metrics.snapshot().get("counters", {})
        saved = int(counters.get("transport.pump.syscalls_saved", 0))
        batches = int(counters.get("transport.pump.batches", 0))

    ratio = best["native"] / best["python"] if best["python"] else 0.0
    first = hub_agg[str(counts[0])]
    last = hub_agg[str(counts[-1])]
    # the GIL-flatness assertion gates on the curve's PEAK over its
    # 1-session anchor: a GIL-bound wire path pins every point at
    # ~1.0x; a batched GIL-released one rises with sessions until the
    # host runs out of cores (a 2-core CI box peaks at 4 sessions and
    # oversubscribes at 16 — the curve itself is the artifact)
    peak = max(hub_agg.values())
    scaling = (peak / first) if first else 0.0
    log(f"bench[wire_pump]: e2e {mib} MiB — native {best['native']:.3f} "
        f"GiB/s vs python {best['python']:.3f} ({ratio:.2f}x); hub agg "
        f"{hub_agg} (peak scaling {scaling:.2f})")
    return {
        "metric": "wire_pump_e2e_throughput",
        "value": round(best["native"], 3),
        "unit": "GiB/s",
        "vs_baseline": None,
        # the ROADMAP item 5 target metric by its own name: host
        # bytes->digest through a real kernel socket, native route
        "e2e_host_gib_s": round(best["native"], 3),
        "python_pump_gib_s": round(best["python"], 3),
        "pump_ratio": round(ratio, 3),
        "volume_mib": mib,
        "reps": reps,
        "hub_sessions": counts,
        "hub_agg_gib_s": hub_agg,
        "hub_agg_1": first,
        "hub_agg_last": last,
        "hub_agg_peak": round(peak, 4),
        "hub_scaling": round(scaling, 3),
        "pump_batches": batches,
        "syscalls_saved": saved,
        "probe": spump.probe_caps(),
        "reduced_config": mib < 64 or counts[-1] < 16,
        "full_config": "64 MiB e2e A/B + hub aggregate at 1/4/16 "
                       "sessions over socketpairs",
    }


# config 14: N-replica gossip convergence — the epidemic anti-entropy
# mesh (ISSUE 15, ROADMAP item 4): rounds/time to byte-identical
# replicas and total wire bytes vs the divergence actually moved, at
# N in {4, 16, 64}


def bench_gossip_converge(quick: bool, backend: str) -> dict:
    import time as _time

    from dat_replication_protocol_tpu.cluster import ClusterSim
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics
    from dat_replication_protocol_tpu.obs.propagation import PROPAGATION
    from dat_replication_protocol_tpu.obs.wirecost import WIRECOST

    ns_env = os.environ.get("BENCH_GOSSIP_N")
    ns = [int(x) for x in ns_env.split(",")] if ns_env else (
        [4, 8] if quick else [4, 16, 64])
    records = int(os.environ.get("BENCH_GOSSIP_RECORDS",
                                 "32" if quick else "192"))
    divergence = int(os.environ.get("BENCH_GOSSIP_DIVERGENCE",
                                    "8" if quick else "24"))
    res: dict = {}
    # the propagation plane LIT (ISSUE 19): this config prices its own
    # overhead by its own gate — exchange_p99_s comes from the plane's
    # board, and the seconds headline carries the lit-path cost
    was_on = obs_metrics.OBS.on
    obs_metrics.enable()
    try:
        for n in ns:
            # clean links: this config measures the protocol's cost,
            # not its robustness (the chaos sweep in tests/ owns
            # that); the fixed seed pins sampling so rounds are
            # reproducible
            PROPAGATION.reset_for_tests()
            WIRECOST.reset_for_tests()
            sim = ClusterSim(n, seed=20_240, chaos=False,
                             records_per=records, divergence=divergence)
            t0 = _time.perf_counter()
            out = sim.run()
            dt = _time.perf_counter() - t0
            if not out["converged"]:
                return {"error": f"gossip mesh n={n} did not converge "
                                 f"within {out['bound']} rounds"}
            # wire_x: total gossip wire over the divergence bytes that
            # HAD to move — the O(diff) headline at mesh scale (1.0
            # would be a perfect oracle; rateless symbols + record
            # framing ride on top)
            wire_x = (sim.wire_bytes / sim.divergence_bytes
                      if sim.divergence_bytes else 0.0)
            p99 = PROPAGATION.exchange_p99()
            # wire cost plane (ISSUE 20): every exchange of this mesh
            # ran lit, so the board ledger holds exactly this n's wire
            goodput, overhead = _wirecost_ratios()
            res[n] = {"rounds": out["rounds"], "seconds": round(dt, 3),
                      "wire_bytes": sim.wire_bytes,
                      "divergence_bytes": sim.divergence_bytes,
                      "wire_x": round(wire_x, 3),
                      "exchange_p99_s": round(p99 or 0.0, 6),
                      "goodput_ratio": goodput,
                      "overhead_ratio": overhead}
            log(f"bench[gossip_converge]: n={n} rounds={out['rounds']} "
                f"{dt:.2f}s wire={sim.wire_bytes} "
                f"(divergence {sim.divergence_bytes}, x{wire_x:.2f}, "
                f"exchange p99 {p99 or 0.0:.4f}s)")
    finally:
        PROPAGATION.reset_for_tests()
        WIRECOST.reset_for_tests()
        obs_metrics.OBS.on = was_on
    top = max(ns)
    return {
        "metric": "gossip_converge_seconds",
        # the headline: wall seconds for the LARGEST mesh to reach
        # byte-identical replicas from full divergence
        "value": res[top]["seconds"],
        "unit": "s",
        "vs_baseline": None,
        "ns": ns,
        "records_per": records,
        "divergence_per": divergence,
        "rounds_top": res[top]["rounds"],
        "wire_x_top": res[top]["wire_x"],
        # the convergence-plane budget fields (ISSUE 19): p99 wall
        # seconds of one lit exchange at the top mesh size, and the
        # rounds the top mesh took to converge — both gated in
        # perf_budgets.json so the plane's own overhead is priced
        "exchange_p99_s": res[top]["exchange_p99_s"],
        "rounds_to_converge": res[top]["rounds"],
        "goodput_ratio": res[top]["goodput_ratio"],
        "overhead_ratio": res[top]["overhead_ratio"],
        **{f"rounds_{n}": res[n]["rounds"] for n in ns},
        **{f"seconds_{n}": res[n]["seconds"] for n in ns},
        **{f"wire_bytes_{n}": res[n]["wire_bytes"] for n in ns},
        **{f"wire_x_{n}": res[n]["wire_x"] for n in ns},
        **{f"exchange_p99_s_{n}": res[n]["exchange_p99_s"] for n in ns},
        "reduced_config": top < 64 or records < 192 or divergence < 24,
        "full_config": "N in {4,16,64}, 192 base + 24 unique records "
                       "per replica, clean links, fixed seed, "
                       "propagation plane lit",
    }


def _edge_client_main(n: int, port: int, wire_hex: str) -> None:
    """The client half of config 15, run as a SUBPROCESS (its own
    RLIMIT_NOFILE budget: N concurrent sessions need N client fds plus
    N server fds, and the container's hard cap cannot carry both sides
    of 10k in one process).  Protocol on the pipe: print ``HELD k``
    when the whole cohort is connected and parked mid-wire, wait for
    ``GO`` on stdin, finish the flood, print one JSON result line."""
    import selectors as _selectors
    import socket as _socket

    wire = bytes.fromhex(wire_hex)
    half = len(wire) // 2
    addr = ("127.0.0.1", port)
    CONNECT_CHUNK = 128  # outstanding connects: stay under the backlog
    sel = _selectors.DefaultSelector()
    # client FSM rows: [sock, state, t_sent, latency, reply_bytes]
    clients = []
    t_ramp0 = time.perf_counter()
    started = 0
    held = 0
    failures = 0
    deadline = time.monotonic() + 300
    # -- ramp: connect everyone, send HALF the wire, park -------------
    while held + failures < n:
        if time.monotonic() > deadline:
            raise TimeoutError(f"edge_scaling ramp stuck at {held}/{n}")
        while started < n and (started - held - failures) < CONNECT_CHUNK:
            s = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
            s.setblocking(False)
            s.connect_ex(addr)
            row = [s, "connecting", 0.0, 0.0, 0]
            clients.append(row)
            sel.register(s, _selectors.EVENT_WRITE, row)
            started += 1
        for skey, _mask in sel.select(0.05):
            row = skey.data
            if row[1] != "connecting":
                continue
            s = row[0]
            err = s.getsockopt(_socket.SOL_SOCKET, _socket.SO_ERROR)
            sel.unregister(s)
            if err:
                s.close()
                row[1] = "failed"
                failures += 1
                continue
            s.sendall(wire[:half])
            row[1] = "held"
            held += 1
    ramp_s = time.perf_counter() - t_ramp0
    print(f"HELD {held}", flush=True)
    if sys.stdin.readline().strip() != "GO":
        raise RuntimeError("edge client: no GO from the bench driver")
    # -- finish flood: the measured phase -----------------------------
    t0 = time.perf_counter()
    reading = 0
    for row in clients:
        if row[1] != "held":
            continue
        s = row[0]
        s.sendall(wire[half:])
        s.shutdown(_socket.SHUT_WR)
        row[1] = "reading"
        row[2] = time.perf_counter()
        sel.register(s, _selectors.EVENT_READ, row)
        reading += 1
    done = 0
    while done < reading:
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"edge_scaling finish stuck at {done}/{reading}")
        for skey, _mask in sel.select(0.05):
            row = skey.data
            s = row[0]
            try:
                data = s.recv(1 << 16)
            except (BlockingIOError, InterruptedError):
                continue
            except OSError:
                data = b""
            if data:
                row[4] += len(data)
                continue
            row[3] = time.perf_counter() - row[2]
            row[1] = "done"
            sel.unregister(s)
            s.close()
            done += 1
    finish_s = time.perf_counter() - t0
    sel.close()
    ok = sum(1 for row in clients if row[1] == "done" and row[4] > 0)
    lats = sorted(row[3] for row in clients if row[1] == "done")
    p99 = lats[max(0, int(0.99 * (len(lats) - 1)))] if lats else 0.0
    print(json.dumps({
        "held": held, "failures": failures, "done": done, "ok": ok,
        "ramp_s": round(ramp_s, 3), "finish_s": round(finish_s, 3),
        "p99_s": round(p99, 4),
    }), flush=True)


def bench_edge_scaling(quick: bool, backend: str) -> dict:
    """Config 15 (ISSUE 17): the C10k claim — 1/100/1k/10k concurrent
    mixed-QoS-class sessions through ONE event-driven edge loop.

    Every client connects and parks mid-wire until the whole cohort is
    admitted (peak table occupancy == N, verified from the loop's own
    snapshot), then the cohort finishes at once: the finish flood is
    the measured phase.  Headline: finish-phase sessions/s at the top
    N; the budget gate additionally holds ``ok_fraction`` at 1.0 and
    admission-ladder counts (rejected/shed) at ZERO — overload
    machinery must stay dark on a properly sized hub, at every scale.
    The client cohort runs in a subprocess (fd budget: N sessions are
    N fds on EACH side).

    ISSUE 18: the run is captured with the obs gate ON so the turn
    profiler is lit — the loop's own per-turn accounting yields
    ``loop_lag_max_s``/``p99_turn_s`` per cohort size, budget-gated at
    the top N.  The flight-deck numbers are therefore measured WITH
    profiler overhead included: the budget holds both the telemetry
    and its cost."""
    import subprocess
    import threading

    import dat_replication_protocol_tpu as protocol
    from dat_replication_protocol_tpu.edge import EdgeLoop
    from dat_replication_protocol_tpu.hub import ReplicationHub
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    ns_env = os.environ.get("BENCH_EDGE_N")
    counts = [int(x) for x in ns_env.split(",")] if ns_env else (
        [1, 100, 1000] if quick else [1, 100, 1000, 10000])

    import resource
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    want = max(counts) + 512
    if soft < want:
        try:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(want, hard), hard))
        except (ValueError, OSError):
            pass
        soft = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
    dropped = [n for n in counts if n + 512 > soft]
    if dropped:
        log(f"bench[edge_scaling]: fd limit {soft} drops counts "
            f"{dropped} (needs 1 fd/session + slack per side)")
        counts = [n for n in counts if n + 512 <= soft] or [1]

    # one tiny session wire, built untimed: a single change frame —
    # this config measures the TABLE (admission, readiness, teardown
    # at scale), not byte throughput (config 13 owns that)
    e = protocol.encode()
    e.change({"key": "edge-bench", "change": 0, "from": 0, "to": 1,
              "value": b"v" * 64})
    e.finalize()
    parts = []
    while True:
        d = e.read(1 << 16)
        if d is None:
            break
        parts.append(d)
    wire = b"".join(parts)

    res: dict = {}
    was_on = obs_metrics.OBS.on
    obs_metrics.enable()  # lit turn profiler: measure WITH the flight deck on
    try:
        for n in counts:
            hub = ReplicationHub(max_sessions=n + 8, linger_s=0.002)
            qos_of = lambda i, peer, mode: \
                "latency" if i % 2 else "throughput"  # noqa: E731
            loop = EdgeLoop(hub, qos_of=qos_of, max_sessions=n,
                            tick=0.02, drain_timeout=60.0)
            port = loop.bind("127.0.0.1", 0)
            server = threading.Thread(target=loop.serve, daemon=True)
            server.start()
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--edge-client", str(n), str(port), wire.hex()],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True)
            try:
                line = proc.stdout.readline().strip()
                if not line.startswith("HELD "):
                    raise RuntimeError(f"edge client died during ramp: "
                                       f"{line!r}")
                held = int(line.split()[1])
                # peak occupancy: every held session sits in the ONE
                # table — wait for the accept side to drain its backlog
                # (held sessions cannot finish: half their wire is
                # missing)
                deadline = time.monotonic() + 120
                peak = loop.snapshot()["sessions"]
                while peak < held and time.monotonic() < deadline:
                    time.sleep(0.01)
                    peak = max(peak, loop.snapshot()["sessions"])
                proc.stdin.write("GO\n")
                proc.stdin.flush()
                out = json.loads(proc.stdout.readline())
                proc.wait(timeout=60)
            finally:
                if proc.poll() is None:
                    proc.kill()
                loop.close()
            server.join(30)
            snap = loop.snapshot()
            hub.close()
            finish_s = out["finish_s"]
            prof = snap["loop"]  # the profiler's own turn accounting
            res[n] = {
                "sessions_s": (round(out["done"] / finish_s, 1)
                               if finish_s else 0.0),
                "p99_s": out["p99_s"],
                "ramp_s": out["ramp_s"],
                "finish_s": finish_s,
                "peak_sessions": peak,
                "ok": out["ok"],
                "admitted": snap["admitted"],
                "rejected": snap["rejected"],
                "shed": snap["shed"],
                "loop_lag_max_s": round(prof["lag_max_s"], 6),
                "p99_turn_s": round(prof["p99_work_s"], 6),
                "loop_turns": prof["turns"],
            }
            log(f"bench[edge_scaling]: n={n} peak={peak} "
                f"{res[n]['sessions_s']}/s "
                f"p99={out['p99_s'] * 1e3:.1f}ms "
                f"(ramp {out['ramp_s']:.2f}s, finish {finish_s:.2f}s, "
                f"ok {out['ok']}, lag_max "
                f"{res[n]['loop_lag_max_s'] * 1e3:.1f}ms, p99 turn "
                f"{res[n]['p99_turn_s'] * 1e3:.1f}ms)")
    finally:
        obs_metrics.OBS.on = was_on
    top = max(counts)
    total_ok = sum(res[n]["ok"] for n in counts)
    return {
        "metric": "edge_scaling_sessions_per_s",
        # the headline: finish-flood completions/s at the LARGEST
        # concurrent cohort
        "value": res[top]["sessions_s"],
        "unit": "sessions/s",
        "vs_baseline": None,
        "ns": counts,
        "wire_bytes": len(wire),
        # the C10k acceptance row: cohort size the ONE table actually
        # held at once, and the clean-completion fraction
        "peak_sessions_top": res[top]["peak_sessions"],
        "ok_fraction": round(total_ok / sum(counts), 6),
        "p99_s_top": res[top]["p99_s"],
        "rejected_total": sum(res[n]["rejected"] for n in counts),
        "shed_total": sum(res[n]["shed"] for n in counts),
        # ISSUE 18 flight-deck rows: worst loop overrun and p99 turn
        # time at the top cohort, straight from the turn profiler
        "loop_lag_max_s_top": res[top]["loop_lag_max_s"],
        "p99_turn_s_top": res[top]["p99_turn_s"],
        **{f"sessions_s_{n}": res[n]["sessions_s"] for n in counts},
        **{f"p99_s_{n}": res[n]["p99_s"] for n in counts},
        **{f"peak_{n}": res[n]["peak_sessions"] for n in counts},
        **{f"loop_lag_max_s_{n}": res[n]["loop_lag_max_s"]
           for n in counts},
        **{f"p99_turn_s_{n}": res[n]["p99_turn_s"] for n in counts},
        "reduced_config": top < 10000,
        "full_config": "1/100/1k/10k concurrent mixed-QoS sessions "
                       "through one edge loop on host, turn profiler "
                       "lit (obs gate ON)",
    }


# ---------------------------------------------------------------------------


BENCHES = {
    "1": ("roundtrip", bench_roundtrip),
    "2": ("replay", bench_replay),
    "3": ("hash", bench_hash),
    "4": ("cdc", bench_cdc),
    "5": ("merkle_diff", bench_merkle),
    "6": ("resume", bench_resume),
    "7": ("wire_batch", bench_wire_batch),
    "8": ("fused_e2e", bench_fused_e2e),
    "9": ("hub_soak", bench_hub_soak),
    "10": ("fanout", bench_fanout),
    "11": ("reconcile_rateless", bench_reconcile_rateless),
    "12": ("snapshot_bootstrap", bench_snapshot_bootstrap),
    "13": ("wire_pump", bench_wire_pump),
    "14": ("gossip_converge", bench_gossip_converge),
    "15": ("edge_scaling", bench_edge_scaling),
}


_state: dict = {"configs": {}, "backend": None, "device": None,
                "backend_error": None}
_emitted = False

# --metrics: attach a per-config obs-registry snapshot to each config's
# result so BENCH_*.json rounds carry attribution (which layer moved),
# not just a headline number.  The registry is reset between configs so
# each snapshot is that config's own story.
_METRICS = {"on": False}


def _metrics_on() -> None:
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    _METRICS["on"] = True
    obs_metrics.enable()


def _attach_metrics(res: dict) -> None:
    """Attach the registry snapshot to one config result (no-op unless
    --metrics), then reset values for the next config."""
    if not _METRICS["on"]:
        return
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    res["metrics"] = obs_metrics.snapshot()
    obs_metrics.REGISTRY.reset()


def _device_telemetry_subset() -> dict:
    """device./backend.-prefixed slice of the live registry — the
    partial device telemetry that rides a failed backend init's
    ``backend_error`` record (ISSUE 5 satellite)."""
    from dat_replication_protocol_tpu.obs import metrics as obs_metrics

    snap = obs_metrics.snapshot()

    def pick(d: dict) -> dict:
        return {k: v for k, v in d.items()
                if k.startswith(("device.", "backend."))}

    return {"counters": pick(snap.get("counters", {})),
            "gauges": pick(snap.get("gauges", {})),
            # device.chiplock.wait lives here — the contention story a
            # failed device run most needs in its post-mortem
            "histograms": pick(snap.get("histograms", {}))}


def _export_config_trace(name: str, trace_dir) -> None:
    """--trace artifact per config: the obs span/event rings exported
    as one Chrome trace JSON (Perfetto-loadable) under
    <trace_dir>/configs/<name>.trace.json, rings cleared after so each
    artifact is that config's own story.  The rings only fill while
    telemetry is on (--metrics / DAT_OBS) — frame spans and joined
    jax-annotation spans alike — so without it the artifact is an
    empty shell; pass --metrics alongside --trace for span content."""
    if not trace_dir:
        return
    try:
        from dat_replication_protocol_tpu.obs import events as obs_events
        from dat_replication_protocol_tpu.obs import tracing as obs_tracing

        try:
            out = os.path.join(trace_dir, "configs", f"{name}.trace.json")
            obs_tracing.export_chrome_trace(out)
            log(f"bench: config {name} trace -> {out}")
        finally:
            # clear even when the export failed: a leftover ring would
            # leak THIS config's spans into the next config's artifact.
            # The engine-select memo resets with the rings — otherwise
            # every config after the first would carry no
            # device.engine.select attribution in its artifact.
            from dat_replication_protocol_tpu.obs import device as obs_device

            obs_tracing.SPANS.clear()
            obs_events.EVENTS.clear()
            obs_device.reset_engine_notes()
    except Exception as e:  # an unwritable dir must not blank the run
        log(f"bench: config {name} trace export failed ({e})")


def _export_config_fleet(name: str, fleet_dir) -> None:
    """--fleet-snapshot artifact per config (ISSUE 11): the same JSON
    record the sidecar's /snapshot endpoint serves — registry metrics,
    jit_sites, watermark links — dumped under
    <fleet_dir>/configs/<name>.fleet.json next to the --trace
    artifacts, so a bench run leaves per-config fleet views an
    `obs fleet` file target (or a human) can read directly.  Like the
    trace export, content needs --metrics/DAT_OBS; dark runs dump an
    honest near-empty shell."""
    try:
        if fleet_dir:
            from dat_replication_protocol_tpu.obs.http import (
                default_snapshot,
            )

            out = os.path.join(fleet_dir, "configs", f"{name}.fleet.json")
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "w", encoding="utf-8") as f:
                json.dump(default_snapshot(), f, default=repr)
                f.write("\n")
            log(f"bench: config {name} fleet view -> {out}")
    except Exception as e:  # an unwritable dir must not blank the run
        log(f"bench: config {name} fleet export failed ({e})")
    finally:
        # like the per-config ring clears, and UNCONDITIONALLY (not
        # only under --fleet-snapshot): a config's watermark links
        # must not leak into the next config's snapshot, and a link's
        # cursor closures must not pin the config's journal buffers
        # for the rest of the run
        from dat_replication_protocol_tpu.obs.watermarks import WATERMARKS

        WATERMARKS.reset_for_tests()


def _emit() -> None:
    """Print the one JSON artifact line from whatever has completed.

    Idempotent; also called by the deadline watchdog, so even a wedged
    device call mid-run leaves a parseable artifact (round 1 left none).
    """
    global _emitted
    if _emitted:
        return
    _emitted = True
    configs = _state["configs"]
    headline = configs.get("hash", {})
    out = {
        # the hash config's own name for it: a BENCH_PLATFORM=cpu run
        # renames it (see main), so a CPU figure never sits under the
        # device metric's name
        "metric": headline.get("metric",
                               "blake2b_batched_blob_hash_throughput"),
        # null, not 0.0, when the headline config produced no number — a
        # fake zero is indistinguishable from a measured failure downstream
        "value": headline.get("value"),
        "unit": "GiB/s",
        "vs_baseline": headline.get("vs_baseline"),
        "backend": _state["backend"],
        # the device every figure above ran on, as jax reports it
        "device": _state["device"],
        "configs": configs,
    }
    if "error" in headline:
        out["error"] = headline["error"]
    if _state["backend_error"]:
        out["backend_error"] = _state["backend_error"]
    print(json.dumps(out), flush=True)


# the configs whose metric is a device metric, in run order (the
# headline hash first, then merkle, then cdc — the largest volume)
_DEVICE_KEYS = ("3", "5", "4")


def _init_backend(force: str | None, deadline_s: float) -> str:
    """Initialise jax in THIS process and return its platform.

    Places the persistent compile cache first, honours
    ``BENCH_PLATFORM``, then brings the backend up under the staged
    init watchdog (``backend.init.*`` events name the stage a slow init
    is in).  Raises whatever jax raises: there is no second process to
    fall back to."""
    import numpy as np

    import jax

    from dat_replication_protocol_tpu.obs.device import BackendInitWatchdog
    from dat_replication_protocol_tpu.utils.cache import enable_compile_cache
    from dat_replication_protocol_tpu.utils.routing import describe_device

    enable_compile_cache()
    if force:
        jax.config.update("jax_platforms", force)
    with BackendInitWatchdog(deadline_s=max(30.0, deadline_s)) as wd:
        wd.stage("platform_probe")
        wd.stage("first_device_call")
        device = describe_device()
        wd.stage("first_compile")
        assert int(np.asarray(jax.jit(lambda: jax.numpy.arange(4))())[3]) == 3
    if wd.fired:
        log(f"bench: backend init exceeded its watchdog (recovered); "
            f"see backend.init.* events")
    _state["backend"] = device["platform"]
    _state["device"] = device
    log(f"bench: backend up in {wd.elapsed_s:.1f}s: {device}")
    return device["platform"]


def _backend_error(e: BaseException) -> dict:
    """Structured ``backend_error`` record for a failed in-process init:
    message, the init stage it died in and when (from the watchdog's
    events, when telemetry is on), plus the device telemetry subset."""
    be: dict = {"message": f"{type(e).__name__}: {e}",
                "stage": None, "elapsed_s": None}
    if _METRICS["on"]:
        from dat_replication_protocol_tpu.obs import events as obs_events

        st = obs_events.EVENTS.last("backend.init.stage")
        if st is not None:
            be["stage"] = st["fields"].get("stage")
            be["elapsed_s"] = st["fields"].get("elapsed_s")
        be["telemetry"] = _device_telemetry_subset()
    return be


def main() -> None:
    import contextlib
    import threading

    if sys.argv[1:2] == ["--edge-client"]:
        # config 15's client cohort, re-invoked as a subprocess: the fd
        # budget (1 fd/session/process) is why this is not a thread
        _edge_client_main(int(sys.argv[2]), int(sys.argv[3]),
                          sys.argv[4])
        return

    quick = "--quick" in sys.argv
    if "--metrics" in sys.argv:
        _metrics_on()
    trace_dir = None
    flight_dir = None
    fleet_dir = None
    args = sys.argv[1:]
    for i, arg in enumerate(args):
        if arg.startswith("--trace="):
            trace_dir = arg.split("=", 1)[1]
        elif arg == "--trace":
            trace_dir = "/tmp/dat_bench_trace"
        elif arg.startswith("--flight-dir="):
            flight_dir = arg.split("=", 1)[1]
        elif arg == "--flight-dir" and i + 1 < len(args) \
                and not args[i + 1].startswith("-"):
            flight_dir = args[i + 1]
        elif arg.startswith("--fleet-snapshot="):
            fleet_dir = arg.split("=", 1)[1]
        elif arg == "--fleet-snapshot" and i + 1 < len(args) \
                and not args[i + 1].startswith("-"):
            fleet_dir = args[i + 1]
    if flight_dir:
        # armed recorder: a stuck backend init (the watchdog below) or
        # any structured session error dumps a post-mortem bundle here
        from dat_replication_protocol_tpu.obs import flight as obs_flight

        obs_flight.arm(flight_dir)
    which = [
        k.strip()
        for k in os.environ.get(
            "BENCH_CONFIGS", "1,2,3,4,5,6,7,8,9,10,11,12,13").split(",")
        if k.strip() in BENCHES
    ]

    # hard deadline: emit whatever completed and exit NON-ZERO — a run
    # cut short is not a result, and the caller must be able to tell
    deadline = float(os.environ.get("BENCH_DEADLINE", 600 if quick else 1800))
    watchdog = threading.Timer(
        deadline, lambda: (log(f"bench: deadline {deadline:.0f}s hit"), _emit(),
                           os._exit(3)),
    )
    watchdog.daemon = True
    watchdog.start()
    # last line of defense: even an uncaught exception anywhere below must
    # still leave a parseable artifact (_emit is idempotent; the watchdog's
    # os._exit path already emits itself)
    import atexit

    atexit.register(_emit)

    def run_config(key: str, backend: str) -> None:
        name, fn = BENCHES[key]
        t0 = time.perf_counter()
        try:
            res = fn(quick, backend)
            res["seconds"] = round(time.perf_counter() - t0, 2)
            # fleet view BEFORE _attach_metrics: that call resets the
            # registry, and the view's whole point is this config's
            # live metrics + watermark links
            _export_config_fleet(name, fleet_dir)
            _attach_metrics(res)
            _state["configs"][name] = res
            log(f"bench: config {key} ({name}) ok in {res['seconds']}s")
        except Exception as e:
            log(f"bench: config {key} ({name}) FAILED: {e}")
            traceback.print_exc(file=sys.stderr)
            err_res = {"error": f"{type(e).__name__}: {e}"}
            _export_config_fleet(name, fleet_dir)
            _attach_metrics(err_res)  # partial-work attribution
            _state["configs"][name] = err_res
        _export_config_trace(name, trace_dir)

    # one process per chip: jax initialises HERE, once, in the process
    # that runs every config.  The host-group configs build
    # decode(backend="tpu") / ReplicationHub() themselves, so on a chip
    # host they touch the device anyway; a second process that needed it
    # would fail or hang.  The device leg (3 hash, 5 merkle, 4 cdc —
    # headline first) refuses to run on a CPU backend unless
    # BENCH_PLATFORM=cpu asked for a functional CPU run: a CPU figure is
    # never written under a device metric's name.
    host_keys = [k for k in which if k not in _DEVICE_KEYS]
    device_keys = sorted((k for k in which if k in _DEVICE_KEYS),
                         key=_DEVICE_KEYS.index)
    force = os.environ.get("BENCH_PLATFORM") or None

    from dat_replication_protocol_tpu.utils.chiplock import chip_lock

    # exclusive chip mutex, held from the first device touch to the last
    # config: a concurrent diagnostic on the same chip contaminates a
    # capture.  Wait a bounded slice of the budget for a peer to finish;
    # if it never does, run anyway and let the artifact SAY contended.
    with chip_lock(max_wait=max(30.0, min(300.0, deadline / 4))) as lease:
        if not lease.uncontended:
            log(f"bench: chip lock contended "
                f"(held={lease.held}, waited {lease.waited_s:.0f}s)")
        try:
            backend = _init_backend(force, min(300.0, deadline / 2))
        except Exception as e:
            log(f"bench: backend init failed: {e}")
            traceback.print_exc(file=sys.stderr)
            backend = None
            _state["backend_error"] = _backend_error(e)
        for key in host_keys:
            run_config(key, "host")
        # --trace wraps the device configs in a jax.profiler capture
        # (open with TensorBoard/Perfetto); library spans from
        # utils.trace annotate pack/dispatch/collect phases
        if trace_dir and device_keys and backend is not None:
            from dat_replication_protocol_tpu.utils.trace import trace_to

            ctx = trace_to(trace_dir)
            log(f"bench: tracing device configs to {trace_dir}")
        else:
            ctx = contextlib.nullcontext()
        with ctx:
            for key in device_keys:
                name = BENCHES[key][0]
                if backend is None:
                    _state["configs"][name] = {
                        "error": _state["backend_error"]["message"],
                        "stage": _state["backend_error"]["stage"]}
                elif backend == "cpu" and force != "cpu":
                    _state["configs"][name] = {
                        "error": "no accelerator: jax initialised 'cpu'; "
                                 "BENCH_PLATFORM=cpu runs this config as a "
                                 "functional CPU check"}
                    log(f"bench: config {key} ({name}) REFUSED: "
                        f"no accelerator")
                else:
                    run_config(key, backend)
                    res = _state["configs"][name]
                    if "error" not in res:
                        res.update(lease.as_fields())
                        if backend == "cpu":
                            res["metric"] = "cpu_functional_" + res["metric"]

    watchdog.cancel()
    _emit()
    failed = sorted(nm for nm, res in _state["configs"].items()
                    if "error" in res)
    if failed or _state["backend_error"]:
        log(f"bench: FAILED configs: {failed}"
            + ("; backend init failed" if _state["backend_error"] else ""))
        sys.exit(1)


if __name__ == "__main__":
    main()
