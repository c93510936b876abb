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


@pytest.mark.parametrize("engine", ["xla-scan", "pallas", "straddle"])
def test_batch_begin_matches_hashlib_on_each_engine(engine, obs_enabled,
                                                    monkeypatch):
    rng = random.Random(27)
    few = [rng.randbytes(rng.choice([129, 200, 256])) for _ in range(3)]
    if engine == "straddle":
        # one call, both engines: a bucket whose padded batch reaches the
        # 512-row floor goes to Pallas, the 3-item bucket beside it to
        # the XLA scan
        _interpreted_pallas(monkeypatch)
        monkeypatch.setattr(b2.jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("DAT_DONATE", "0")
        many = [rng.randbytes(rng.choice([0, 1, 64, 128]))
                for _ in range(b2._PALLAS_MIN_ITEMS // 2 + 1)]
        payloads, use = many[:100] + few + many[100:], None
        reached = {"pallas:1", "xla-scan:2"}
    else:
        if engine == "pallas":
            _interpreted_pallas(monkeypatch)
        payloads = few + [b"", rng.randbytes(128), rng.randbytes(5)]
        use = engine == "pallas"
        reached = {f"{engine}:1", f"{engine}:2"}
    assert b2.blake2b_batch(payloads, use_pallas=use) == \
        [host(p) for p in payloads]
    assert set(b2._BUCKETS.snapshot()) == reached


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
