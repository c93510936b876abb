"""A blob's bytes are copied once between the receive slab and the
staging row (ISSUE 30): the decoder hands the digest path views of what
it was written, the pipeline queues a blob as its pieces, the pack
copies each piece into the item's row and drops it there.
"""

import gc
import hashlib
import socket
import threading
import weakref

import numpy as np
import pytest

from dat_replication_protocol_tpu.backend import tpu_backend
from dat_replication_protocol_tpu.backend.tpu_backend import (
    DigestPipeline,
    TpuDecoder,
)
from dat_replication_protocol_tpu.hub import ReplicationHub
from dat_replication_protocol_tpu.ops import blake2b as b2
from dat_replication_protocol_tpu.runtime import native
from dat_replication_protocol_tpu.session import pump
from dat_replication_protocol_tpu.utils.payload import PayloadParts
from dat_replication_protocol_tpu.wire.change_codec import encode_change
from dat_replication_protocol_tpu.wire.framing import TYPE_BLOB, TYPE_CHANGE
from dat_replication_protocol_tpu.wire.varint import encode_uvarint


def _h(data) -> bytes:
    return hashlib.blake2b(bytes(data), digest_size=32).digest()


def _hashlib_batch(payloads):
    assert all(type(p) is bytes for p in payloads)  # a caller's engine
    return [_h(p) for p in payloads]


def frame(type_id: int, payload: bytes) -> bytes:
    return encode_uvarint(len(payload) + 1) + bytes([type_id]) + payload


def _blob(n: int, salt: int) -> bytes:
    return bytes((i * 131 + salt) & 0xFF for i in range(n))


def _change(i: int) -> bytes:
    return encode_change({"key": f"k{i}", "change": i, "from": 0, "to": 1,
                          "value": b"v" * 40})


BLOB_LENS = [1, 300, 0, 5000, 70000, 3, 2048]


def _wire():
    """Blobs of every awkward size between change rows.  Returns the
    wire, the expected (kind, seq, digest) stream, and each blob's
    (header start, payload start, payload end) on the wire."""
    out, want, spans = [], [], []
    pos = 0
    for i, n in enumerate(BLOB_LENS):
        c = _change(i)
        f = frame(TYPE_CHANGE, c)
        out.append(f)
        pos += len(f)
        want.append(("change", i, _h(c)))
        b = _blob(n, i)
        f = frame(TYPE_BLOB, b)
        out.append(f)
        spans.append((pos, pos + len(f) - n, pos + len(f)))
        pos += len(f)
        want.append(("blob", i, _h(b)))
    return b"".join(out), want, spans


def _cuts(kind: str, wire: bytes, spans) -> list[int]:
    if kind == "whole":
        return []
    if kind == "header":      # inside every blob's header
        return [h + 1 for h, _, _ in spans]
    if kind == "first":       # after every blob's first payload byte
        return [p + 1 for _, p, e in spans if e > p]
    if kind == "last":        # before every blob's last payload byte
        return [e - 1 for _, p, e in spans if e > p]
    if kind == "three":       # the 70,000-byte blob lies across three
        _, p, e = spans[BLOB_LENS.index(70000)]
        return [p + 20000, p + 50000]
    if kind == "every-100":
        return list(range(100, len(wire), 100))
    raise AssertionError(kind)


def _slabs(wire: bytes, cuts: list[int]):
    """Fresh memory per piece, as the pumps hand it over."""
    edges = [0] + sorted(set(cuts)) + [len(wire)]
    for a, b in zip(edges, edges[1:]):
        yield memoryview(np.frombuffer(wire[a:b], np.uint8).copy())


def _decoder(pipe_kind: str, handler: bool, got: list, chunks: list):
    hub = None
    if pipe_kind == "private":
        pipe = DigestPipeline(max_batch=4, max_inflight=2)
    elif pipe_kind == "private-own-engine":
        pipe = DigestPipeline(hash_batch=_hashlib_batch, max_batch=4)
    else:
        hub = ReplicationHub(hash_batch=_hashlib_batch, linger_s=0.0)
        pipe = hub.register("s")
    dec = TpuDecoder(pipeline=pipe)
    dec.on_digest(lambda kind, seq, d: got.append((kind, seq, d)))
    dec.change(lambda ch, done: done())
    if handler:
        def on_blob(blob, done):
            mine = []
            chunks.append(mine)
            blob.on_data(mine.append)
            blob.on_end(done)
        dec.blob(on_blob)
    return dec, hub


@pytest.mark.parametrize("pipe_kind", ["private", "private-own-engine",
                                       "hub"])
@pytest.mark.parametrize("handler", [False, True],
                         ids=["drained", "handler"])
@pytest.mark.parametrize("cut", ["whole", "header", "first", "last",
                                 "three", "every-100"])
def test_one_wire_every_slicing_gives_the_same_digests(cut, handler,
                                                       pipe_kind):
    wire, want, spans = _wire()
    got, chunks = [], []
    dec, hub = _decoder(pipe_kind, handler, got, chunks)
    try:
        for slab in _slabs(wire, _cuts(cut, wire, spans)):
            assert dec.write(slab)
        dec.end()
        assert dec.finished
    finally:
        if hub is not None:
            hub.close()
    assert got == want  # every digest, in submit order
    if handler:  # a registered handler still reads bytes, all of them
        assert all(type(c) is bytes for mine in chunks for c in mine)
        assert [b"".join(mine) for mine in chunks] == \
            [_blob(n, i) for i, n in enumerate(BLOB_LENS)]


@pytest.mark.skipif(not native.available(), reason="no native library")
@pytest.mark.parametrize("pipe_kind", ["private", "hub"])
@pytest.mark.parametrize("handler", [False, True],
                         ids=["drained", "handler"])
def test_the_native_indexed_route_gives_the_same_digests(handler, pipe_kind,
                                                         monkeypatch):
    monkeypatch.setenv("DAT_PUMP", "native")
    wire, want, _ = _wire()
    wire, want = wire * 3, None
    got, chunks = [], []
    dec, hub = _decoder(pipe_kind, handler, got, chunks)
    a, b = socket.socketpair()
    try:
        t = threading.Thread(target=lambda: (a.sendall(wire),
                                             a.shutdown(socket.SHUT_WR)))
        t.start()
        pump.recv_pump(dec, b.fileno())
        t.join(30)
        assert dec.finished
    finally:
        a.close()
        b.close()
        if hub is not None:
            hub.close()
    k = len(BLOB_LENS)
    blobs = [d for kind, _, d in got if kind == "blob"]
    assert blobs == [_h(_blob(n, i)) for i, n in enumerate(BLOB_LENS)] * 3
    assert [(kind, seq) for kind, seq, _ in got] == \
        [(kind, i) for i in range(3 * k) for kind in ("change", "blob")]


@pytest.mark.parametrize("n_head", [30, 0], ids=["bulk", "streaming"])
def test_a_handler_that_raises_mid_blob_still_ends_the_blob(n_head):
    payload = _blob(500, 9)
    head = b"".join(frame(TYPE_CHANGE, _change(i)) for i in range(n_head))
    wire = head + frame(TYPE_BLOB, payload)  # the blob LAST: no healer
    got, seen, boom, ended = [], [], [True], []
    dec = TpuDecoder(pipeline=DigestPipeline(max_batch=64))
    dec.on_digest(lambda kind, seq, d: got.append((kind, d)))
    dec.change(lambda ch, done: done())

    def on_data(c):
        seen.append(c)
        if boom[0]:
            boom[0] = False
            raise RuntimeError("reader")

    dec.blob(lambda blob, done: (blob.on_data(on_data),
                                 blob.on_end(lambda: (ended.append(1),
                                                      done()))))
    with pytest.raises(RuntimeError, match="reader"):
        dec.write(wire)
    dec.write(b"")
    dec.end()
    assert dec.finished and ended == [1]
    assert b"".join(seen) == payload
    assert [d for kind, d in got if kind == "blob"] == [_h(payload)]


@pytest.mark.parametrize(
    "case", ["drained-private", "handler-private", "drained-hub",
             "drained-hub-whole-bytes"])
def test_the_copied_counter_says_where_a_copy_was_made(case, obs_enabled):
    """``decoder.blob.copied.bytes`` over ``decoder.blob.bytes``: 0 where
    the mechanism is engaged, 1 where a reader wanted ``bytes``; behind
    the hub (ISSUE 34) the blobs smaller than the change frame in front
    of them, which would pin more of the slab than they carry."""
    wire, _, spans = _wire()
    total = sum(BLOB_LENS)
    joined = sum(n for n in BLOB_LENS
                 if n < len(frame(TYPE_CHANGE, _change(0))))
    assert 0 < joined < 100
    got, chunks = [], []
    dec, hub = _decoder("hub" if "hub" in case else "private",
                        case.startswith("handler"), got, chunks)
    try:
        if case == "drained-hub-whole-bytes":
            dec.write(wire)  # immutable bytes, whole: still views of it
        else:
            for slab in _slabs(wire, _cuts("three", wire, spans)):
                dec.write(slab)
        dec.end()
    finally:
        if hub is not None:
            hub.close()
    counters = obs_enabled.REGISTRY.snapshot()["counters"]
    assert counters["decoder.blob.bytes"] == total
    assert counters.get("decoder.blob.copied.bytes", 0) == \
        (0 if case == "drained-private" else
         total if case == "handler-private" else joined)


# -- the staging row is where the pieces are joined ---------------------------

WIDE = 2 * b2._FILL_WHOLE_MAX


def _in_pieces(payload: bytes, cuts, as_views: bool):
    edges = [0] + list(cuts) + [len(payload)]
    pieces = [payload[a:b] for a, b in zip(edges, edges[1:])]
    if as_views:
        pieces = [memoryview(np.frombuffer(p, np.uint8).copy()).toreadonly()
                  if p else memoryview(b"") for p in pieces]
    return PayloadParts(pieces)


@pytest.mark.parametrize("as_views", [False, True], ids=["bytes", "views"])
@pytest.mark.parametrize(
    "lens, nblocks, rows",
    [
        ([0, 1, 127, 128, 129, 255, 256], 2, 8),
        ([256, 256, 256], 2, 4),
        ([WIDE, 0, WIDE - 1, WIDE // 2 + 1, 5], WIDE // 128, 8),
    ],
    ids=["narrow", "full", "wide"],
)
def test_parts_stage_to_the_rows_of_the_joined_payloads(lens, nblocks, rows,
                                                        as_views):
    payloads = [_blob(n, 7 * i + 1) for i, n in enumerate(lens)]
    mixed = []
    for i, p in enumerate(payloads):
        if i % 3 == 0:
            mixed.append(p)  # a whole item between the ones in pieces
        elif i % 3 == 1:
            mixed.append(_in_pieces(p, [len(p) // 3, len(p) // 2], as_views))
        else:
            mixed.append(_in_pieces(p, [], as_views))
    want = np.full((rows, nblocks * 128), 0x55, dtype=np.uint8)
    got = np.full((rows, nblocks * 128), 0xAA, dtype=np.uint8)  # stale
    want_lengths = b2.stage_payloads(payloads, want)
    lengths = b2.stage_payloads(mixed, got)
    assert np.array_equal(got, want)  # tails and padding rows zero alike
    assert np.array_equal(lengths, want_lengths)
    for i, p in enumerate(payloads):
        assert got[i, :len(p)].tobytes() == p
        assert not got[i, len(p):].any()
    assert not got[len(payloads):].any()


@pytest.mark.parametrize("n_items", [3, 64, 200])
@pytest.mark.parametrize("as_views", [False, True], ids=["bytes", "views"])
def test_the_host_engine_hashes_parts_piece_by_piece(n_items, as_views):
    payloads = [_blob(17 * i % 700, i) for i in range(n_items)]
    mixed = [p if i % 2 else _in_pieces(p, [len(p) // 4, len(p) // 2],
                                        as_views)
             for i, p in enumerate(payloads)]
    if as_views:  # and one whole view, as a blob inside one slab arrives
        mixed[1] = memoryview(payloads[1])
    assert tpu_backend._host_hash_batch(mixed) == [_h(p) for p in payloads]
    assert len(mixed[0]) == len(payloads[0])  # counted like bytes


@pytest.mark.parametrize("engine", ["default", "hash_batch", "hash_begin"])
def test_a_callers_engine_keeps_receiving_bytes(engine):
    seen = []

    def hash_batch(ps):
        seen.extend(type(p) for p in ps)
        return [_h(p) for p in ps]

    kw = {"hash_batch": hash_batch} if engine == "hash_batch" else \
        {"hash_begin": lambda ps: (lambda out=hash_batch(ps): out)} \
        if engine == "hash_begin" else {}
    pipe = DigestPipeline(max_batch=8, **kw)
    got = []
    slab = memoryview(np.frombuffer(_blob(900, 3), np.uint8).copy())
    pipe.submit_parts([slab[:100], slab[100:400]], got.append)
    pipe.submit_parts([slab[400:]], got.append)      # one view: no parts
    pipe.submit_parts([b"whole"], got.append)        # bytes stays bytes
    pipe.submit_parts([], got.append)                # a zero-length blob
    pipe.submit(b"plain", got.append)
    assert pipe._payloads[2] == b"whole" and type(pipe._payloads[2]) is bytes
    pipe.flush()
    assert got == [_h(_blob(900, 3)[:400]), _h(_blob(900, 3)[400:]),
                   _h(b"whole"), _h(b""), _h(b"plain")]
    assert pipe.hashed_bytes == 900 + 5 + 5
    assert set(seen) <= {bytes}


# -- what a queue pins ---------------------------------------------------------


def _pinned(pipe: DigestPipeline) -> int:
    """Bytes of the distinct slabs the queued payloads' views keep."""
    slabs = {}
    for p in pipe._payloads:
        pieces = p.parts if type(p) is PayloadParts else (p,)
        for piece in pieces:
            if type(piece) is memoryview:
                slabs[id(piece.obj)] = memoryview(piece.obj).nbytes
    return sum(slabs.values())


@pytest.mark.parametrize("blob_len", [16, 256, 3000])
@pytest.mark.parametrize("slab", [4096, 32768])
@pytest.mark.parametrize("cap", [100_000, 300_000])
def test_a_queue_pins_its_byte_cap_and_the_slabs_at_its_ends(cap, slab,
                                                             blob_len):
    """Small blobs between change rows: a view keeps its whole slab, so
    counting payload bytes alone would let a queue of 256-byte blobs pin
    a slab each.  The slabs are charged as the views move past them:
    whatever the mix, a queue holds at most ``max_batch_bytes`` and the
    two slabs at its ends (the first is shared with the batch before,
    the last is still being filled)."""
    wire = b"".join(
        frame(TYPE_CHANGE, _change(i)) * (1 + i % 37)
        + frame(TYPE_BLOB, _blob(blob_len, i)) for i in range(600))
    pipe = DigestPipeline(max_batch=1 << 20, max_batch_bytes=cap,
                          max_inflight=2)
    dec = TpuDecoder(pipeline=pipe)
    got = []
    dec.on_digest(lambda kind, seq, d: got.append((kind, seq)))
    worst = 0
    for a in range(0, len(wire), slab):
        dec.write(memoryview(np.frombuffer(wire[a:a + slab],
                                           np.uint8).copy()))
        worst = max(worst, _pinned(pipe))
        assert _pinned(pipe) <= cap + 2 * slab
    assert worst > slab  # the queue did hold views of several slabs
    assert pipe.dispatches > 1  # and the charge is what closed batches
    dec.end()
    assert [s for k, s in got if k == "blob"] == list(range(600))


@pytest.mark.parametrize("handler", [False, True],
                         ids=["drained", "handler"])
def test_a_batch_in_flight_holds_lengths_not_bytes(handler):
    pipe = DigestPipeline(max_batch=1 << 20, max_inflight=4)
    # the host engine's digests exist at dispatch and would be delivered
    # there: a closure that cannot say so keeps the batch in flight
    begin = pipe._hash_begin
    pipe._hash_begin = lambda ps: (lambda collect=begin(ps): collect())
    dec = TpuDecoder(pipeline=pipe)
    got = []
    dec.on_digest(lambda kind, seq, d: got.append(d))
    if handler:
        dec.blob(lambda blob, done: (blob.on_data(lambda c: None),
                                     blob.on_end(done)))
    payloads = [_blob(5000, i) for i in range(12)]
    wire = b"".join(frame(TYPE_BLOB, p) for p in payloads)
    slabs = [np.frombuffer(wire[a:a + 8192], np.uint8).copy()
             for a in range(0, len(wire), 8192)]
    refs = [weakref.ref(s) for s in slabs]
    while slabs:
        dec.write(memoryview(slabs.pop(0)))
    assert pipe._payloads and pipe.inflight == 0
    pipe.dispatch()
    assert pipe.inflight == 1 and not pipe._payloads and got == []
    for entries, _collect, _batch, _t0 in pipe._inflight:
        assert [type(item) for item, _cb, _tag in entries] == [int] * 12
    gc.collect()
    # the pack was the last reader: every slab is gone but the one the
    # pipeline remembers having charged (none where the decoder copied)
    assert sum(r() is not None for r in refs) == (0 if handler else 1)
    pipe.flush()
    assert got == [_h(p) for p in payloads]
    assert pipe.hashed_bytes == 12 * 5000
