"""Fault-injection conformance sweep: the session survives chaos.

The conformance scenarios (test_session_conformance.py — changes, blobs,
interleaved corked blobs, changes parked behind blobs) run as ONE
session wire through the deterministic fault injector
(session/faults.py) and the resumable reconnect driver
(session/reconnect.py).  The contract under test (ISSUE 2 acceptance):
for every seed, an injected disconnect-class fault (drop / truncation /
stall / pathological re-segmentation) ends in either

* **byte-identical decoded output after resume** — same events, same
  order, same bytes, no duplicates, no gaps; or
* **exactly one structured ProtocolError** with frame/byte context;

and NEVER a hang: each case runs under a hard watchdog timeout.

The tier-1 subset sweeps seeds 0..19; the ``slow``-marked soak covers
200 seeds.  Corruption-class faults (byte flips) get targeted tests —
a flipped header must ERROR (not resume), and the error must carry
context.
"""

from __future__ import annotations

import threading

import pytest

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
from dat_replication_protocol_tpu.wire.framing import ProtocolError

HARD_TIMEOUT = 30.0  # per-case watchdog: "never a hang", enforced


def _build_wire() -> bytes:
    """One session covering every conformance scenario: a bulk change
    run (the native-indexed path), two interleaved corked blobs, a
    change parked behind an open blob, a multi-KiB blob (mid-payload
    fault territory), and trailing changes."""
    e = protocol.encode()
    j = WireJournal()
    e.attach_journal(j)
    for i in range(24):  # >= 16: exercises the bulk fast loop
        e.change({"key": f"bulk-{i}", "change": i, "from": i, "to": i + 1,
                  "value": b"v%03d" % i})
    b1 = e.blob(11)
    b2 = e.blob(11)
    b1.write(b"hello ")
    b2.write(b"HELLO ")
    b1.write(b"world")
    b2.write(b"WORLD")
    b1.end()
    b2.end()
    big = e.blob(3000)
    big.write(b"x" * 1700)
    e.change({"key": "parked", "change": 99, "from": 0, "to": 1,
              "value": b"after-blob"})
    big.end(b"y" * 1300)
    for i in range(8):
        e.change({"key": f"tail-{i}", "change": i, "from": i, "to": i + 1})
    e.finalize()
    while e.read(4096) is not None:
        pass
    return j.read_from(0)


_WIRE = _build_wire()


def _fresh_decoder(backend: str = "host"):
    """Decoder + its event sink; events capture order, keys, and bytes."""
    dec = protocol.decode(backend=backend)
    events: list = []
    dec.change(lambda c, done: (
        events.append(("change", c.key, c.value)), done()))
    dec.blob(lambda b, done: b.collect(
        lambda data: (events.append(("blob", data)), done())))
    if backend == "tpu":
        dec.on_digest(lambda kind, seq, d: events.append(("digest", kind, seq, d)))
    return dec, events


def _expected(backend: str = "host"):
    dec, events = _fresh_decoder(backend)
    for off in range(0, len(_WIRE), 777):
        dec.write(_WIRE[off:off + 777])
    dec.end()
    assert dec.finished
    return events


_EXPECTED = _expected()


def _with_watchdog(fn):
    """Run ``fn`` on a worker thread under the hard timeout; re-raise its
    outcome here.  A case that neither returns nor raises is a HANG —
    the exact failure class this suite exists to exclude."""
    box: dict = {}

    def run():
        try:
            box["ret"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to the test
            box["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(HARD_TIMEOUT)
    assert not t.is_alive(), f"HANG: case still running after {HARD_TIMEOUT}s"
    if "err" in box:
        raise box["err"]
    return box["ret"]


def _run_seed(seed: int, backend: str = "host"):
    dec, events = _fresh_decoder(backend)

    def source(ckpt, failures):
        remaining = len(_WIRE) - ckpt.wire_offset
        plan = FaultPlan.for_sweep(seed, remaining, attempt=failures)
        return FaultyReader(bytes_reader(_WIRE[ckpt.wire_offset:]), plan)

    def drive():
        return run_resumable(
            source, dec,
            BackoffPolicy(base=0.0005, cap=0.005, max_retries=8, seed=seed),
            chunk_size=1024,
            expected_total=len(_WIRE),
            stall_timeout=HARD_TIMEOUT / 2,
        )

    try:
        stats = _with_watchdog(drive)
    except ProtocolError as e:
        # the error arm: exactly one structured error, with context
        assert e.offset is not None, f"unstructured ProtocolError: {e}"
        return None, None
    return stats, events


# -- tier-1 subset: 20 seeds, disconnect-class faults -----------------------

@pytest.mark.parametrize("seed", range(20))
def test_sweep_resumes_byte_identical(seed):
    stats, events = _run_seed(seed)
    # disconnect-class faults are absorbable by design: every seed must
    # converge (the plan generator goes clean after attempt 1), and the
    # decoded session must be byte-identical — no duplicate deliveries,
    # no gaps, no reordering across however many resumes happened
    assert stats is not None, "disconnect-class fault must resume, not error"
    assert events == _EXPECTED
    assert stats["reconnects"] == len(stats["faults"])


@pytest.mark.parametrize("seed", [3, 11])
def test_sweep_tpu_backend_digest_state_survives_resume(seed):
    expected = _expected(backend="tpu")
    stats, events = _run_seed(seed, backend="tpu")
    assert stats is not None
    # digests included: every (kind, seq) exactly once, values identical
    # to the unfaulted run — the checkpoint's digest counters mean a
    # resume neither re-hashes delivered frames nor skips sequence ids
    assert events == expected


# -- fused + double-buffered digest pipeline under faults (ISSUE 7) ---------

@pytest.mark.parametrize("seed", [2, 7, 13])
def test_sweep_fused_pipeline_digests_exactly_once(seed, monkeypatch):
    """Mid-blob/mid-run faults through the DONATED, double-buffered
    digest pipeline: the decoder's pipeline runs the jitted batch engine
    with donated input buffers and two batches in flight across the
    fault.  Digests must arrive exactly once per (kind, seq) with values
    identical to the unfaulted run — a donated buffer whose HBM was
    recycled mid-resume must never leak a stale block into the next
    dispatch's hashes."""
    import warnings

    from dat_replication_protocol_tpu.backend.tpu_backend import (
        DigestPipeline,
    )

    monkeypatch.setenv("DAT_DEVICE_HASH", "1")  # the jitted batch engine
    monkeypatch.setenv("DAT_DONATE", "1")       # donated staging buffers
    warnings.simplefilter("ignore")  # CPU jax warns per ignored donation

    def fresh():
        # small batch + inflight bounds: several batches genuinely in
        # flight while the fault machinery stalls/truncates/resumes
        dec = protocol.decode(
            backend="tpu",
            pipeline=DigestPipeline(max_batch=4, max_inflight=2),
        )
        events: list = []
        dec.change(lambda c, done: (
            events.append(("change", c.key, c.value)), done()))
        dec.blob(lambda b, done: b.collect(
            lambda data: (events.append(("blob", data)), done())))
        dec.on_digest(
            lambda kind, s, d: events.append(("digest", kind, s, d)))
        return dec, events

    exp_dec, expected = fresh()
    for off in range(0, len(_WIRE), 777):
        exp_dec.write(_WIRE[off:off + 777])
    exp_dec.end()
    assert exp_dec.finished

    dec, events = fresh()

    def source(ckpt, failures):
        remaining = len(_WIRE) - ckpt.wire_offset
        plan = FaultPlan.for_sweep(seed, remaining, attempt=failures)
        return FaultyReader(bytes_reader(_WIRE[ckpt.wire_offset:]), plan)

    stats = _with_watchdog(lambda: run_resumable(
        source, dec,
        BackoffPolicy(base=0.0005, cap=0.005, max_retries=8, seed=seed),
        chunk_size=1024, expected_total=len(_WIRE),
        stall_timeout=HARD_TIMEOUT / 2,
    ))
    assert stats is not None
    digests = [e for e in events if e[0] == "digest"]
    keys = [(k, s) for _, k, s, _ in digests]
    assert len(keys) == len(set(keys)), "duplicate digest delivery"
    # values byte-identical, order preserved: of the digests and of the
    # frames.  Where a digest falls between frames is the device's
    # timing (a batch is delivered once its closure reports ready)
    assert digests == [e for e in expected if e[0] == "digest"]
    assert [e for e in events if e[0] != "digest"] \
        == [e for e in expected if e[0] != "digest"]


# -- soak: 200 seeds (slow) -------------------------------------------------

@pytest.mark.slow
def test_sweep_soak_200_seeds():
    for seed in range(20, 220):
        stats, events = _run_seed(seed)
        assert stats is not None, f"seed {seed} errored on a resumable fault"
        assert events == _EXPECTED, f"seed {seed} diverged"


# -- corruption class: must ERROR with context, never resume ----------------

def test_flipped_header_type_id_errors_with_context():
    # frame 0's header is [varint len][type id]; the type id of the first
    # frame sits at byte 1 for single-byte-varint frames
    def source(ckpt, failures):
        plan = FaultPlan(seed=1, flip_at=1 - ckpt.wire_offset
                         if ckpt.wire_offset <= 1 else None, flip_mask=0x44)
        return FaultyReader(bytes_reader(_WIRE[ckpt.wire_offset:]), plan)

    dec, _events = _fresh_decoder()
    with pytest.raises(ProtocolError) as ei:
        _with_watchdog(lambda: run_resumable(
            source, dec, BackoffPolicy(base=0, max_retries=2, seed=0),
            expected_total=len(_WIRE), stall_timeout=5))
    err = ei.value
    assert "unknown type" in str(err)
    assert err.frame == 0 and err.offset is not None


def test_retries_exhausted_is_one_structured_error():
    def source(ckpt, failures):
        plan = FaultPlan(seed=2, drop_at=50)  # every attempt dies at 50
        return FaultyReader(bytes_reader(_WIRE[ckpt.wire_offset:]), plan)

    dec, _events = _fresh_decoder()
    policy = BackoffPolicy(base=0.0001, max_retries=3, seed=0)
    with pytest.raises(ProtocolError) as ei:
        _with_watchdog(lambda: run_resumable(
            source, dec, policy, expected_total=len(_WIRE), stall_timeout=5))
    err = ei.value
    assert "after 4 transport fault(s)" in str(err)
    assert isinstance(err.cause, TransportFault)
    assert err.offset is not None and err.frame is not None


def test_truncation_is_detected_not_silent():
    """A clean-looking EOF short of the sender's declared length must
    reconnect (detected truncation), finishing byte-identical."""
    calls = {"n": 0}

    def source(ckpt, failures):
        calls["n"] += 1
        plan = FaultPlan(seed=3,
                         truncate_at=len(_WIRE) // 3 if failures == 0 else None)
        return FaultyReader(bytes_reader(_WIRE[ckpt.wire_offset:]), plan)

    dec, events = _fresh_decoder()
    stats = _with_watchdog(lambda: run_resumable(
        source, dec, BackoffPolicy(base=0.0001, max_retries=2, seed=0),
        expected_total=len(_WIRE), stall_timeout=5))
    assert calls["n"] == 2 and stats["reconnects"] == 1
    assert "truncated" in stats["faults"][0]
    assert events == _EXPECTED


def test_mid_blob_disconnect_resumes_without_redelivery():
    """Drop inside the 3000-byte blob's payload: the checkpoint carries
    blob_offset > 0 and the resumed connection continues the SAME frame
    — delivered blob bytes must concatenate to exactly the payload."""
    # find a drop point inside the big blob: after ~70% of the wire
    drop_at = int(len(_WIRE) * 0.55)
    ckpts = []

    def source(ckpt, failures):
        ckpts.append(ckpt)
        plan = FaultPlan(seed=4, max_segment=256,
                         drop_at=(drop_at - ckpt.wire_offset)
                         if failures == 0 else None)
        return FaultyReader(bytes_reader(_WIRE[ckpt.wire_offset:]), plan)

    dec, events = _fresh_decoder()
    stats = _with_watchdog(lambda: run_resumable(
        source, dec, BackoffPolicy(base=0.0001, max_retries=2, seed=0),
        expected_total=len(_WIRE), stall_timeout=5))
    assert stats["reconnects"] == 1
    assert events == _EXPECTED
    # the second connection's checkpoint observed the fault point
    assert ckpts[1].wire_offset == drop_at


def _build_batch_wire() -> bytes:
    """The negotiated-session twin of ``_build_wire``: columnar
    ChangeBatch frames (several, so faults land INSIDE column blocks),
    interleaved blobs forcing flushes, and a per-record tail."""
    from dat_replication_protocol_tpu import BatchPolicy, CAP_CHANGE_BATCH

    e = protocol.encode(peer_caps=CAP_CHANGE_BATCH,
                        batch_policy=BatchPolicy(max_rows=40))
    j = WireJournal()
    e.attach_journal(j)
    for i in range(100):  # 2.5 batch frames' worth before the blob flush
        e.change({"key": f"bulk-{i % 16}", "change": i, "from": i,
                  "to": i + 1, "value": b"v%03d" % i,
                  "subset": "s" if i % 3 else None})
    big = e.blob(3000)
    big.write(b"x" * 1700)
    e.change({"key": "parked", "change": 99, "from": 0, "to": 1,
              "value": b"after-blob"})
    big.end(b"y" * 1300)
    for i in range(30):
        e.change({"key": f"tail-{i % 4}", "change": i, "from": i,
                  "to": i + 1})
    e.finalize()
    while e.read(4096) is not None:
        pass
    return j.read_from(0)


_BATCH_WIRE = _build_batch_wire()


def _expected_on(wire: bytes):
    dec, events = _fresh_decoder()
    for off in range(0, len(wire), 777):
        dec.write(wire[off:off + 777])
    dec.end()
    assert dec.finished
    return events


_BATCH_EXPECTED = _expected_on(_BATCH_WIRE)


def _run_seed_on(wire: bytes, seed: int):
    dec, events = _fresh_decoder()

    def source(ckpt, failures):
        remaining = len(wire) - ckpt.wire_offset
        plan = FaultPlan.for_sweep(seed, remaining, attempt=failures)
        return FaultyReader(bytes_reader(wire[ckpt.wire_offset:]), plan)

    def drive():
        return run_resumable(
            source, dec,
            BackoffPolicy(base=0.0005, cap=0.005, max_retries=8, seed=seed),
            chunk_size=256,  # small chunks: disconnects land mid-frame
            expected_total=len(wire),
            stall_timeout=HARD_TIMEOUT / 2,
        )

    try:
        stats = _with_watchdog(drive)
    except ProtocolError as e:
        assert e.offset is not None, f"unstructured ProtocolError: {e}"
        return None, None
    return stats, events


@pytest.mark.parametrize("seed", range(20))
def test_sweep_batch_frames_resume_exactly_once(seed):
    """Disconnect-class faults against a ChangeBatch-framed session:
    every seed converges and the decoded rows are exactly-once in order
    — resume across a batch boundary neither redelivers nor drops a
    row of the interrupted frame."""
    stats, events = _run_seed_on(_BATCH_WIRE, seed)
    assert stats is not None, "disconnect-class fault must resume, not error"
    assert events == _BATCH_EXPECTED


def _batch_frame_extent():
    """(payload_start, payload_len) of the first ChangeBatch frame."""
    import numpy as np

    from dat_replication_protocol_tpu.runtime import replay
    from dat_replication_protocol_tpu.wire.framing import TYPE_CHANGE_BATCH

    idx = replay.split_frames(np.frombuffer(_BATCH_WIRE, np.uint8))
    f = int(np.nonzero(idx.ids == TYPE_CHANGE_BATCH)[0][0])
    return int(idx.starts[f]), int(idx.lens[f])


def test_truncate_inside_batch_column_block_redelivers_exactly_once():
    start, flen = _batch_frame_extent()
    cut = start + flen // 2  # middle of the column block
    calls = {"n": 0}

    def source(ckpt, failures):
        calls["n"] += 1
        plan = FaultPlan(seed=7, truncate_at=(cut - ckpt.wire_offset)
                         if failures == 0 else None)
        return FaultyReader(
            bytes_reader(_BATCH_WIRE[ckpt.wire_offset:]), plan)

    dec, events = _fresh_decoder()
    stats = _with_watchdog(lambda: run_resumable(
        source, dec, BackoffPolicy(base=0.0001, max_retries=2, seed=0),
        expected_total=len(_BATCH_WIRE), stall_timeout=5))
    assert calls["n"] == 2 and stats["reconnects"] == 1
    assert events == _BATCH_EXPECTED  # every row exactly once


def test_flip_inside_batch_column_block_never_hangs():
    """A flipped byte inside the column block either trips the batch
    decoder's structural validation (ONE structured error with context)
    or lands in a value heap byte (delivered corrupt — the documented
    wire-layer limit, same as a blob payload flip).  Either way: never
    a hang, never a duplicate."""
    start, flen = _batch_frame_extent()
    for probe in (5, flen // 3, flen - 2):
        flip_at = start + probe

        def source(ckpt, failures, flip_at=flip_at):
            plan = FaultPlan(seed=9, flip_at=flip_at - ckpt.wire_offset,
                             flip_mask=0x40)
            return FaultyReader(
                bytes_reader(_BATCH_WIRE[ckpt.wire_offset:]), plan)

        dec, events = _fresh_decoder()
        try:
            stats = _with_watchdog(lambda: run_resumable(
                source, dec,
                BackoffPolicy(base=0, max_retries=0, seed=0),
                expected_total=len(_BATCH_WIRE), stall_timeout=5))
        except ProtocolError as e:
            assert e.offset is not None and e.frame is not None
            continue
        assert stats is not None
        # completed: rows delivered at most once (corrupt content is
        # possible; duplicates/hangs are not)
        keys = [ev for ev in events if ev[0] == "change"]
        assert len(keys) <= len(
            [ev for ev in _BATCH_EXPECTED if ev[0] == "change"])


# -- rateless reconciliation under chaos (ISSUE 10) --------------------------
#
# The anti-entropy contract: a faulted symbol stream either completes
# with the EXACT symmetric difference after resume, or raises ONE
# structured ProtocolError — never a wrong diff.  The initiator's wire
# (BEGIN + paced symbol batches + the requested records as ChangeBatch
# frames) is recorded once from a healthy run and replayed through the
# fault injector into a fresh responder per seed.


def _build_reconcile_wire():
    from dat_replication_protocol_tpu.runtime.reconcile_driver import (
        RatelessReplica,
        ResponderState,
    )
    from dat_replication_protocol_tpu.wire import reconcile_codec as rcc
    from dat_replication_protocol_tpu.wire.framing import CAP_CHANGE_BATCH, \
        CAP_RECONCILE

    keys = [f"rc-{i:04d}" for i in range(150)]
    a_recs = [{"key": k, "change": i, "from": i, "to": i + 1,
               "value": b"v:" + k.encode()}
              for i, k in enumerate(keys + ["a-only-1", "a-only-2"])]
    b_recs = [{"key": k, "change": i, "from": i, "to": i + 1,
               "value": b"v:" + k.encode()}
              for i, k in enumerate(keys + ["b-only-1"])]
    a = RatelessReplica(a_recs)
    state = ResponderState(RatelessReplica(b_recs))
    e = protocol.encode(peer_caps=CAP_RECONCILE | CAP_CHANGE_BATCH)
    j = WireJournal()
    e.attach_journal(j)
    payload = rcc.encode_begin(a.n)
    e.reconcile_frame(payload)
    state.handle(rcc.decode_reconcile(payload))
    syms = a.coded_symbols()
    sent, m = 0, 16
    while True:
        payload = rcc.encode_symbols(sent, syms.extend(m)[sent:])
        e.reconcile_frame(payload)
        sent = m
        replies = state.handle(rcc.decode_reconcile(payload))
        last = rcc.decode_reconcile(replies[-1])
        if last.kind == rcc.RC_DONE:
            rows = a.rows_for_digests(last.digests)
            e.change_many(a.records_for_rows(rows))
            break
        assert last.kind == rcc.RC_MORE
        m *= 2
    e.finalize()
    while e.read(4096) is not None:
        pass
    return j.read_from(0), b_recs


_RC_WIRE, _RC_B_RECS = _build_reconcile_wire()


def _fresh_reconcile_responder():
    from dat_replication_protocol_tpu.runtime.reconcile_driver import (
        RatelessReplica,
        ResponderState,
    )

    state = ResponderState(RatelessReplica(_RC_B_RECS))
    dec = protocol.decode()
    dec.reconcile(lambda msg, done: (state.handle(msg), done()))
    dec.change(lambda c, done: (state.note_remote_record(c), done()))
    return dec, state


def _rc_expected():
    dec, state = _fresh_reconcile_responder()
    for off in range(0, len(_RC_WIRE), 777):
        dec.write(_RC_WIRE[off:off + 777])
    dec.end()
    assert dec.finished
    digests, signs = state.result()
    diff = sorted((bytes(d), int(s)) for d, s in zip(digests, signs))
    recs = sorted(str(c) for c in state.remote_records)
    assert len(diff) == 3 and len(recs) == 2  # 2 a-only + 1 b-only
    return diff, recs


_RC_EXPECTED = _rc_expected()


def _run_reconcile_seed(seed: int):
    dec, state = _fresh_reconcile_responder()

    def source(ckpt, failures):
        remaining = len(_RC_WIRE) - ckpt.wire_offset
        plan = FaultPlan.for_sweep(seed, remaining, attempt=failures)
        return FaultyReader(bytes_reader(_RC_WIRE[ckpt.wire_offset:]), plan)

    def drive():
        return run_resumable(
            source, dec,
            BackoffPolicy(base=0.0005, cap=0.005, max_retries=8, seed=seed),
            chunk_size=256,  # small chunks: faults land mid-symbol-run
            expected_total=len(_RC_WIRE),
            stall_timeout=HARD_TIMEOUT / 2,
        )

    try:
        stats = _with_watchdog(drive)
    except ProtocolError as e:
        assert e.offset is not None, f"unstructured ProtocolError: {e}"
        return None, None
    try:
        digests, signs = state.result()
    except ProtocolError as e:
        assert e.offset is not None, f"unstructured ProtocolError: {e}"
        return None, None
    diff = sorted((bytes(d), int(s)) for d, s in zip(digests, signs))
    recs = sorted(str(c) for c in state.remote_records)
    return stats, (diff, recs)


@pytest.mark.parametrize("seed", range(20))
def test_sweep_reconcile_resumes_exact_diff(seed):
    """Disconnect-class faults inside the symbol stream: every seed
    must converge after resume with the EXACT symmetric difference and
    the exact record set — a resumed symbol stream continues (the
    decoder's accumulated symbols survive the transport), it never
    restarts or double-counts a run."""
    stats, out = _run_reconcile_seed(seed)
    assert stats is not None, "disconnect-class fault must resume, not error"
    assert out == _RC_EXPECTED


@pytest.mark.slow
def test_sweep_reconcile_soak_100_seeds():
    wrong = []
    for seed in range(20, 120):
        stats, out = _run_reconcile_seed(seed)
        if stats is not None and out != _RC_EXPECTED:
            wrong.append(seed)  # the one outcome the contract forbids
    assert not wrong, f"seeds {wrong} delivered a WRONG diff"


def _rc_symbol_frame_extent():
    """(payload_start, payload_len) of the first SYMBOLS frame."""
    import numpy as np

    from dat_replication_protocol_tpu.runtime import replay
    from dat_replication_protocol_tpu.wire.framing import TYPE_RECONCILE

    idx = replay.split_frames(np.frombuffer(_RC_WIRE, np.uint8))
    rc_frames = np.nonzero(idx.ids == TYPE_RECONCILE)[0]
    f = int(rc_frames[1])  # frame 0 is BEGIN; 1 is the first symbol run
    return int(idx.starts[f]), int(idx.lens[f])


def test_flip_inside_symbol_frame_never_delivers_wrong_diff():
    """A flipped byte inside a coded-symbol run must end in ONE
    structured ProtocolError (structural validation, a failed decode,
    or the end-of-stream incompleteness check) — recovering a wrong
    element needs a 64-bit checksum collision, so a completed decode is
    trusted and must equal the truth."""
    start, flen = _rc_symbol_frame_extent()
    for probe in (0, 3, flen // 2, flen - 1):
        flip_at = start + probe

        def source(ckpt, failures, flip_at=flip_at):
            plan = FaultPlan(seed=13, flip_at=flip_at - ckpt.wire_offset,
                             flip_mask=0x20)
            return FaultyReader(
                bytes_reader(_RC_WIRE[ckpt.wire_offset:]), plan)

        dec, state = _fresh_reconcile_responder()
        try:
            _with_watchdog(lambda: run_resumable(
                source, dec, BackoffPolicy(base=0, max_retries=0, seed=0),
                expected_total=len(_RC_WIRE), stall_timeout=5))
            digests, signs = state.result()
        except ProtocolError as e:
            assert e.offset is not None, f"unstructured: {e}"
            continue
        diff = sorted((bytes(d), int(s)) for d, s in zip(digests, signs))
        assert diff == _RC_EXPECTED[0], f"flip at +{probe} changed the diff"


def test_truncate_inside_symbol_frame_resumes_symbol_stream():
    """Truncation mid-symbol-run: the resumed connection continues the
    SAME symbol stream from the checkpoint byte — the peeler sees every
    cell exactly once and decodes the exact diff."""
    start, flen = _rc_symbol_frame_extent()
    cut = start + flen // 2
    calls = {"n": 0}

    def source(ckpt, failures):
        calls["n"] += 1
        plan = FaultPlan(seed=17, truncate_at=(cut - ckpt.wire_offset)
                         if failures == 0 else None)
        return FaultyReader(bytes_reader(_RC_WIRE[ckpt.wire_offset:]), plan)

    dec, state = _fresh_reconcile_responder()
    stats = _with_watchdog(lambda: run_resumable(
        source, dec, BackoffPolicy(base=0.0001, max_retries=2, seed=0),
        expected_total=len(_RC_WIRE), stall_timeout=5))
    assert calls["n"] == 2 and stats["reconnects"] == 1
    digests, signs = state.result()
    diff = sorted((bytes(d), int(s)) for d, s in zip(digests, signs))
    assert diff == _RC_EXPECTED[0]
    assert sorted(str(c) for c in state.remote_records) == _RC_EXPECTED[1]


def test_payload_flip_is_undetected_at_wire_layer():
    """Documented failure-model limit (ROBUSTNESS.md): a flipped byte
    inside a blob payload does not violate framing — the session
    completes with CORRUPT content.  The digest pipeline, not the wire
    layer, is the end-to-end integrity answer; this test pins the limit
    so a future in-band checksum shows up as a deliberate contract
    change."""
    # flip a byte deep inside the big blob's payload
    flip_at = int(len(_WIRE) * 0.55)

    def source(ckpt, failures):
        plan = FaultPlan(seed=5, flip_at=flip_at - ckpt.wire_offset)
        return FaultyReader(bytes_reader(_WIRE[ckpt.wire_offset:]), plan)

    dec, events = _fresh_decoder()
    stats = _with_watchdog(lambda: run_resumable(
        source, dec, BackoffPolicy(base=0, max_retries=0, seed=0),
        expected_total=len(_WIRE), stall_timeout=5))
    assert stats is not None and dec.finished
    assert events != _EXPECTED  # corrupt — and the wire layer cannot know
