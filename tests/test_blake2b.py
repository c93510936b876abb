"""Device BLAKE2b vs the host reference implementation (hashlib).

SURVEY.md §7 step 3: "validate digests against a host reference
implementation". Covers empty input, sub-block, exact-block, multi-block,
variable lengths in one padded batch, and non-default digest sizes.
"""

import functools
import hashlib
import random

import jax
import numpy as np
import pytest

from dat_replication_protocol_tpu.backend.tpu_backend import DigestPipeline
from dat_replication_protocol_tpu.obs.events import EVENTS
from dat_replication_protocol_tpu.ops import blake2b as b2
from dat_replication_protocol_tpu.ops import blake2b_pallas as b2p


def host(p: bytes, n: int = 32) -> bytes:
    return hashlib.blake2b(p, digest_size=n).digest()


@pytest.mark.parametrize(
    "payload",
    [
        b"",
        b"abc",
        b"a" * 127,
        b"b" * 128,
        b"c" * 129,
        b"d" * 256,
        bytes(range(256)) * 17,  # multi-block, non-uniform bytes
    ],
    ids=["empty", "abc", "127", "128", "129", "256", "4352"],
)
def test_single_payload_matches_hashlib(payload):
    assert b2.blake2b_batch([payload]) == [host(payload)]


def test_mixed_lengths_one_batch():
    rng = random.Random(7)
    payloads = [
        bytes(rng.getrandbits(8) for _ in range(rng.choice([0, 1, 63, 128, 200, 1000])))
        for _ in range(32)
    ]
    assert b2.blake2b_batch(payloads) == [host(p) for p in payloads]


def test_digest_sizes():
    for n in (16, 20, 32, 48, 64):
        assert b2.blake2b_batch([b"hello world"], digest_size=n) == [
            host(b"hello world", n)
        ]


def test_large_payload_multiblock():
    p = bytes(range(256)) * 4096  # 1 MiB
    assert b2.blake2b_batch([p]) == [host(p)]


def test_order_preserved_across_buckets():
    # items alternate between very different sizes -> different buckets,
    # output order must still match submit order
    payloads = [b"x" * (1 if i % 2 else 5000) for i in range(10)]
    assert b2.blake2b_batch(payloads) == [host(p) for p in payloads]


def test_packing_roundtrip_shapes():
    mh, ml, lengths = b2.pack_payloads([b"abc", b"y" * 130])
    assert mh.shape == (2, 2, 16) and ml.shape == (2, 2, 16)
    assert list(lengths) == [3, 130]


# -- staging: one copy per item, the word split on the device (ISSUE 27) -----

WIDE = 2 * b2._FILL_WHOLE_MAX  # a slot zeroed by row tails, not one fill


def _blob(n: int, salt: int) -> bytes:
    return bytes((i * 131 + salt) & 0xFF for i in range(n))


@pytest.mark.parametrize(
    "lens, nblocks, rows",
    [
        ([0], 1, 1),
        ([1], 1, 1),
        ([127], 1, 2),
        ([128], 1, 1),
        ([129], 2, 1),
        ([256, 256], 2, 2),                       # full slots: no tail
        ([0, 1, 127, 128, 129, 255, 256], 2, 8),  # mixed, one pad row
        ([WIDE, 0, WIDE - 1, WIDE // 2 + 1], WIDE // 128, 8),  # tail path
    ],
    ids=["0", "1", "127", "128", "129", "full", "mixed", "wide"],
)
def test_staged_words_split_on_device_equal_pack_payloads(lens, nblocks, rows):
    payloads = [_blob(n, 7 * i + 1) for i, n in enumerate(lens)]
    buf = np.full((rows, nblocks * 128), 0xAA, dtype=np.uint8)  # stale bytes
    lengths = b2.stage_payloads(payloads, buf)
    mh_d, ml_d = jax.jit(b2.split_words)(buf.view("<u4"))
    padded = payloads + [b""] * (rows - len(payloads))
    mh, ml, want = b2.pack_payloads(padded, nblocks=nblocks)
    assert np.array_equal(np.asarray(mh_d), mh)
    assert np.array_equal(np.asarray(ml_d), ml)
    assert lengths.dtype == np.uint32 and list(lengths) == list(want)


def test_stage_refuses_a_payload_wider_than_its_slot():
    with pytest.raises(ValueError, match="nblocks=1 < required 2"):
        b2.pack_payloads([b"x" * 129], nblocks=1)


def _interpreted_pallas(monkeypatch):
    """The Pallas route of ``blake2b_batch_begin`` on the CPU: the same
    jitted raw-words entry points, run by the interpreter."""
    for name in ("blake2b_words_pallas", "blake2b_words_pallas_donated"):
        monkeypatch.setattr(
            b2p, name, functools.partial(getattr(b2p, name), interpret=True))


@pytest.mark.parametrize("engine", ["xla-scan", "pallas", "tpu-default",
                                    "cpu-default"])
def test_batch_begin_matches_hashlib_on_each_engine(engine, obs_enabled,
                                                    monkeypatch):
    rng = random.Random(27)
    few = [rng.randbytes(rng.choice([129, 200, 256])) for _ in range(3)]
    if engine.endswith("-default"):
        # use_pallas=None: ONE engine for the whole call, by backend —
        # a 3-item bucket beside a 257-item one takes the same route
        if engine == "tpu-default":
            _interpreted_pallas(monkeypatch)
            monkeypatch.setattr(b2.jax, "default_backend", lambda: "tpu")
            monkeypatch.setenv("DAT_DONATE", "0")
        many = [rng.randbytes(rng.choice([0, 1, 64, 128]))
                for _ in range(257)]
        payloads, use = many[:100] + few + many[100:], None
        name = "pallas" if engine == "tpu-default" else "xla-scan"
    else:
        if engine == "pallas":
            _interpreted_pallas(monkeypatch)
        payloads = few + [b"", rng.randbytes(128), rng.randbytes(5)]
        use, name = engine == "pallas", engine
    assert b2.blake2b_batch(payloads, use_pallas=use) == \
        [host(p) for p in payloads]
    rows = b2._BUCKETS.snapshot()
    assert set(rows) == {f"{name}:1", f"{name}:2"}
    # a slot this narrow has one declared row count, whatever the items
    assert {r["padded_items"] for r in rows.values()} == {1024}
    # and the call noted ONE engine, not one per bucket
    assert [e["fields"]["engine"]
            for e in EVENTS.events("device.engine.select")
            if e["fields"]["component"] == "blake2b.batch"] == [name]


def _slot_payloads(rng, nblocks, n_items):
    """``n_items`` seeded payloads that all land in the ``nblocks``
    bucket, the block-edge lengths first: for one block the empty
    message, one byte, a whole block; for wider slots the first byte
    past the half slot, a whole block past it, a block plus one, the
    last byte short of the slot, the whole slot."""
    lo = (nblocks // 2) * 128
    edges = [0, 1, 127, 128] if nblocks == 1 else \
        [lo + 1, lo + 128, lo + 129, nblocks * 128 - 1, nblocks * 128]
    return [rng.randbytes(edges[i % len(edges)]) for i in range(n_items)]


@pytest.mark.parametrize(
    "nblocks,n_items,rows",
    [(1, 4, 1024), (16, 1, 1024), (16, 1024, 1024),      # narrow: one count
     (128, 1, 256), (128, 257, 512), (128, 513, 1024),   # 16 KiB slot
     (1024, 1, 32), (1024, 33, 64)],                     # 128 KiB slot
    ids=lambda v: str(v),
)
def test_each_declared_row_count_matches_hashlib_and_the_scan(
        nblocks, n_items, rows, obs_enabled, monkeypatch):
    assert rows in b2.declared_rows(nblocks)
    _interpreted_pallas(monkeypatch)
    payloads = _slot_payloads(random.Random(29 * nblocks + n_items),
                              nblocks, n_items)
    want = [host(p) for p in payloads]
    assert b2.blake2b_batch(payloads, use_pallas=True) == want
    assert b2.blake2b_batch(payloads, use_pallas=False) == want
    assert b2._BUCKETS.snapshot() == {
        f"{e}:{nblocks}": {"dispatches": 1, "items": n_items,
                           "padded_items": rows}
        for e in ("pallas", "xla-scan")}


@pytest.mark.parametrize("engine", ["xla-scan", "pallas"])
def test_every_item_count_of_a_narrow_slot_is_one_program(engine, obs_enabled,
                                                          monkeypatch):
    if engine == "pallas":
        _interpreted_pallas(monkeypatch)
    rng = random.Random(31)
    traces = obs_enabled.REGISTRY.counter("device.jit.traces")
    # a digest size no other test asks for: the jit cache is the
    # process's, and a program an earlier test built would count nothing
    size = 28 if engine == "pallas" else 24
    for n in (1, 2, 3, 31, 32, 33, 511, 512, 513, 1024):
        payloads = [rng.randbytes(rng.randrange(1025, 2049))
                    for _ in range(n)]
        assert b2.blake2b_batch(payloads, size, engine == "pallas") \
            == [host(p, size) for p in payloads]
    assert traces.value == 1
    assert b2._BUCKETS.snapshot() == {f"{engine}:16": {
        "dispatches": 10, "items": 2662, "padded_items": 10 * 1024}}


@pytest.mark.parametrize("nblocks", [1 << k for k in range(17)])
def test_staging_bound_holds_for_every_slot_width(nblocks):
    """Slots of 128 B to 8 MiB: one row count up to 4 KiB, and no bucket
    stages more than max(4 MiB, 32 x slot, 4 x its payload bytes)."""
    slot = nblocks * 128
    declared = b2.declared_rows(nblocks)
    assert declared[-1] == 1024
    assert all(b == 2 * a for a, b in zip(declared, declared[1:]))
    assert (len(declared) == 1) == (slot <= 4096)
    assert declared[0] == min(1024, max(32, (4 << 20) // slot))
    least_payload = 0 if nblocks == 1 else slot // 2 + 1
    for n in (1, 2, 3, 31, 32, 33, 63, 64, 65, 511, 512, 513, 1023, 1024):
        rows = b2.batch_rows(n, nblocks)
        assert rows in declared and rows >= n
        assert rows == next(r for r in declared if r >= n)
        assert rows * slot <= max(4 << 20, 32 * slot, 4 * n * least_payload)
    # past a full tile: whole tiles, still under four times the payload
    assert b2.batch_rows(1025, nblocks) == 2048
    assert b2.batch_rows(5000, nblocks) == 8192


class _Fence:
    def __init__(self, ready: bool):
        self.ready = ready

    def is_ready(self) -> bool:
        return self.ready


@pytest.mark.parametrize("case", ["in-flight", "done", "other-shape",
                                  "bound"])
def test_stage_pool_hands_out_only_what_the_device_is_done_with(case):
    pool = b2._StagePool(max_bytes=3 * 1024)
    first = pool.take((4, 256))
    fence = _Fence(ready=case != "in-flight")
    pool.give(first, fence)
    if case == "in-flight":
        assert pool.take((4, 256)) is not first    # a fresh buffer instead
        fence.ready = True
        assert pool.take((4, 256)) is first        # and kept for later
    elif case == "done":
        assert pool.take((4, 256)) is first
        assert pool.take((4, 256)) is not first    # taken means gone
    elif case == "other-shape":
        assert pool.take((8, 128)) is not first
        assert pool.take((4, 256)) is first
    else:
        more = [np.empty((4, 256), np.uint8) for _ in range(3)]
        for buf in more:                     # 4 KiB given, 3 KiB kept
            pool.give(buf, _Fence(True))
        assert pool._bytes == 3 * 1024
        assert [id(b) for b, _ in pool._parked] == [id(b) for b in more]


@pytest.mark.parametrize("width", [256, WIDE], ids=["fill-whole", "row-tails"])
def test_reused_staging_three_batches_in_flight(width, monkeypatch):
    """Reuse hazard: three batches of one shape, different contents, each
    collected only after the next two were dispatched."""
    monkeypatch.setattr(b2, "_STAGE_POOL", b2._StagePool(1 << 24))
    batches = [[_blob(width - k, 16 * r + k) for k in range(4)]
               for r in range(3)]
    collects = [b2.blake2b_batch_begin(b) for b in batches]
    for batch, collect in zip(batches, collects):
        assert collect() == [host(p) for p in batch]


@pytest.mark.parametrize("width", [256, WIDE], ids=["fill-whole", "row-tails"])
def test_stale_tail_of_a_reused_buffer_is_zeroed(width, obs_enabled,
                                                 monkeypatch):
    """Full-slot payloads, then the same shape with short and empty ones:
    the second batch is laid into the first's buffer and hashes right."""
    monkeypatch.setattr(b2, "_STAGE_POOL", b2._StagePool(1 << 24))
    full = [_blob(width, k) for k in range(4)]
    assert b2.blake2b_batch(full) == [host(p) for p in full]
    # collected, so the fence is ready: the next take is a reuse.  3 items
    # in the same (4, width) bucket: the 4th row is batch padding
    short = [_blob(width // 2 + 1, 9), _blob(width - 1, 5),
             _blob(width // 2 + 3, 2)]
    assert b2.blake2b_batch(short) == [host(p) for p in short]
    counters = obs_enabled.snapshot()["counters"]
    assert counters["digest.stage.alloc"] == 1
    assert counters["digest.stage.reuse"] == 1


@pytest.mark.parametrize("max_batch", [3, 4])
def test_pipeline_of_two_inflight_delivers_in_submit_order(max_batch,
                                                           monkeypatch):
    monkeypatch.setenv("DAT_DEVICE_HASH", "1")
    pipe = DigestPipeline(max_batch=max_batch, max_inflight=2)
    payloads = [_blob((37 * i) % 300, i) for i in range(14)]
    got = []
    for p in payloads:
        pipe.submit(p, got.append)
    pipe.flush()
    assert got == [host(p) for p in payloads]
