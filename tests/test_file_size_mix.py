"""The served digest path under a folder of mixed file sizes: one batch
that spans every slot width (128 B to 8 MiB, seventeen programs),
over-threshold blobs hashed on the host stream beside it, digests in
submit order; and a one-chip engine that builds no program per item
count (its digests are fetched whole and the items' rows picked on the
host)."""

import hashlib
import random

import jax.monitoring

import dat_replication_protocol_tpu as protocol
from dat_replication_protocol_tpu.backend.tpu_backend import (
    DigestPipeline,
    TpuDecoder,
)
from dat_replication_protocol_tpu.obs import device as obs_device
from dat_replication_protocol_tpu.obs import metrics as obs_metrics
from dat_replication_protocol_tpu.obs.tracing import SPANS
from dat_replication_protocol_tpu.ops import blake2b as blake2b_mod

KIB, MIB = 1 << 10, 1 << 20
# one blob a slot width: 1 block (128 B) ... 65,536 blocks (8 MiB)
WIDTH_SIZES = [100] + [(128 << k) - 1 for k in range(1, 16)] + [4 * MIB + 7]
# above the widest of them, so that the 8 MiB slot is still a batch's;
# the served default is 8 MiB
THRESHOLD = 4 * MIB + 4 * KIB
OVER_SIZES = [THRESHOLD, 5 * MIB + 3]
SLAB = 1 * MIB   # what one decoder write carries, as a receive slab does


def _h(data) -> bytes:
    return hashlib.blake2b(data, digest_size=32).digest()


def _wire(blobs: list) -> bytes:
    enc = protocol.encode()
    for b in blobs:
        enc.blob(len(b)).end(b)
    enc.finalize()
    out = []
    while (piece := enc.read()) is not None:
        out.append(piece)
    return b"".join(out)


def test_the_widths_cover_every_slot_and_the_stream_rule():
    widths = {blake2b_mod._bucket_nblocks(-(-n // blake2b_mod.BLOCK_BYTES))
              for n in WIDTH_SIZES}
    assert widths == {1 << k for k in range(17)}
    assert max(WIDTH_SIZES) < THRESHOLD <= min(OVER_SIZES)


def test_a_session_of_every_width_and_two_streams(obs_enabled):
    """One lit session through ``TpuDecoder`` + ``DigestPipeline`` on the
    batch engine: 17 slot widths in ONE dispatch, two blobs past the
    threshold on the host stream between them; every digest equals
    hashlib's, in submit order, and the bucket table counts the bytes
    the kernel was handed — not the streamed ones."""
    rng = random.Random(41)
    sizes = WIDTH_SIZES + OVER_SIZES
    rng.shuffle(sizes)
    blobs = [rng.randbytes(n) for n in sizes]
    pipe = DigestPipeline(hash_begin=blake2b_mod.blake2b_batch_begin)
    dec = TpuDecoder(pipeline=pipe, stream_threshold=THRESHOLD)
    got = []
    dec.on_digest(lambda kind, seq, d: got.append((kind, seq, d)))
    wire = _wire(blobs)
    for at in range(0, len(wire), SLAB):
        dec.write(wire[at:at + SLAB])
    dec.end()
    assert dec.finished and not dec.destroyed
    assert got == [("blob", i, _h(b)) for i, b in enumerate(blobs)]
    assert pipe.dispatches == 1
    rows = obs_device.BUCKETS.snapshot()
    assert len(rows) == 17
    assert {r["dispatches"] for r in rows.values()} == {1}
    assert sum(r["payload_bytes"] for r in rows.values()) == sum(WIDTH_SIZES)
    hists = obs_metrics.snapshot()["histograms"]
    assert hists["span.digest.dispatch.seconds"]["count"] == 1
    # one span a piece of a streamed blob: each crosses several writes
    streamed = SPANS.spans("digest.stream")
    assert len(streamed) >= len(OVER_SIZES) * (THRESHOLD // SLAB)
    assert hists["span.digest.stream.seconds"]["count"] == len(streamed)


def test_a_dark_dispatch_notes_no_bucket():
    assert not obs_metrics.OBS.on
    before = obs_device.BUCKETS.snapshot()
    payloads = [b"x" * 100, b"y" * 300]
    assert blake2b_mod.blake2b_batch_begin(payloads, use_pallas=False)() \
        == [_h(p) for p in payloads]
    assert obs_device.BUCKETS.snapshot() == before


def test_one_chip_builds_no_program_per_item_count(slot_bytes=128):
    """Item counts 1 … 64 in one slot width: one declared row count, so
    every call after the first runs the program the first built — no
    backend compile at all, where a device-side ``hh[:n]`` built two
    slice programs for each new count (64 of them here: what this test
    reads on a tree from before the change)."""
    compiles = []

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    rng = random.Random(slot_bytes)
    low = slot_bytes // 2 + 1
    payloads = [rng.randbytes(rng.randrange(low, slot_bytes + 1))
                for _ in range(64)]
    first = blake2b_mod.blake2b_batch_begin(payloads[:1], use_pallas=False)
    assert first() == [_h(payloads[0])]
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for n in range(1, 65):
            got = blake2b_mod.blake2b_batch_begin(payloads[:n],
                                                  use_pallas=False)()
            assert got == [_h(p) for p in payloads[:n]], n
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
