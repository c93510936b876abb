"""The hub parks a blob as the pieces it arrived in (ISSUE 34): views of
the receive slabs wait in the session's queue, the dispatcher's pack is
the first and only place their bytes are copied, and the budget is kept
on what those views pin, not on what they carry.
"""

import gc
import threading
import time
import weakref

import numpy as np
import pytest
from test_blob_parts import _blob, _change, _h, frame

from dat_replication_protocol_tpu.backend.tpu_backend import TpuDecoder
from dat_replication_protocol_tpu.hub import (
    HubBusy,
    ReplicationHub,
    SessionShed,
)
from dat_replication_protocol_tpu.parallel import mesh as pmesh
from dat_replication_protocol_tpu.utils.payload import PayloadParts
from dat_replication_protocol_tpu.wire.framing import TYPE_BLOB, TYPE_CHANGE

HARD_TIMEOUT = 30
HEADER = 4  # bytes in front of every piece in its slab, as a frame's


def _until(pred, what: str):
    deadline = time.monotonic() + HARD_TIMEOUT
    while not pred():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def _views(payload: bytes, cuts, keep=None) -> list:
    """The payload as read-only views, each piece in a slab of its own
    behind HEADER bytes of something else; `keep` collects the slabs."""
    edges = [0] + list(cuts) + [len(payload)]
    out = []
    for a, b in zip(edges, edges[1:]):
        slab = np.frombuffer(b"\xee" * HEADER + payload[a:b], np.uint8).copy()
        if keep is not None:
            keep.append(slab)
        out.append(memoryview(slab)[HEADER:].toreadonly())
    return out


FORMS = {
    "two-slabs": lambda p: _views(p, [len(p) // 3]),
    "three-slabs": lambda p: _views(p, [len(p) // 4, len(p) // 2]),
    "one-view": lambda p: _views(p, []),
    "bytes": lambda p: [p],
}


def _parking_hub(**kw) -> ReplicationHub:
    """A hub that dispatches nothing until a session flushes: what is
    submitted stays in its session's queue, to be looked at."""
    kw.setdefault("window_bytes", 1 << 40)
    kw.setdefault("window_items", 1 << 30)
    kw.setdefault("parked_budget", 1 << 40)
    return ReplicationHub(linger_s=3600.0, max_batch=1 << 20, **kw)


# -- digests -------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["default", "hash_batch", "mesh"])
@pytest.mark.parametrize("form", list(FORMS))
def test_a_blob_in_any_form_gets_its_digest_in_submit_order(form, engine,
                                                            monkeypatch):
    """Views of two and of three slabs, one view, `bytes`: the same
    digest, between the change rows it was submitted between.  An engine
    that cannot take pieces — a caller's `hash_batch` — is handed joined
    `bytes`, by the dispatcher; the mesh's engine is the served one laid
    over the chips and says it takes pieces (`takes_parts`), so it is
    handed the blob as the hub parked it and its pack is the one copy."""
    seen = []
    if engine == "hash_batch":
        def hash_batch(ps):
            seen.extend(type(p) for p in ps)
            return [_h(p) for p in ps]
        hub = ReplicationHub(hash_batch=hash_batch, linger_s=0.0)
    elif engine == "mesh":
        monkeypatch.setenv("DAT_DEVICE_HASH", "1")
        real = pmesh.sharded_hash_engine

        def spied_engine(m):
            begin = real(m)

            def spy(ps):
                seen.extend(type(p) for p in ps)
                return begin(ps)
            spy.takes_parts = begin.takes_parts
            return spy
        monkeypatch.setattr(pmesh, "sharded_hash_engine", spied_engine)
        hub = ReplicationHub(mesh=4, linger_s=0.0)
        assert hub.mesh_devices == 4
    else:
        hub = ReplicationHub(linger_s=0.0)
    got, want = [], []
    rows = [b"row-%d" % i * 7 for i in range(5)]
    try:
        s = hub.register("s")
        for i in range(3):
            s.submit_many(rows, lambda tag, d: got.append(("change", tag, d)),
                          tag_base=5 * i)
            want += [("change", 5 * i + k, _h(r)) for k, r in enumerate(rows)]
            blob = _blob(700 + 300 * i, i)
            s.submit_parts(FORMS[form](blob),
                           lambda tag, d: got.append(("blob", tag, d)), i)
            want.append(("blob", i, _h(blob)))
        s.flush()
        s.close()
    finally:
        hub.close()
    assert got == want
    if engine == "hash_batch":
        assert seen and set(seen) == {bytes}
    elif engine == "mesh":
        # the pieces it parked: the rows are bytes, the blob is what the
        # session submitted — never a joined copy of a blob in pieces
        parked_as = {"two-slabs": PayloadParts, "three-slabs": PayloadParts,
                     "one-view": memoryview}.get(form)
        assert [t for t in seen if t is not bytes] \
            == ([parked_as] * 3 if parked_as else [])


# -- the join rule, and its witness --------------------------------------------


def _decode_through_hub(wire: bytes, slab: int, hub: ReplicationHub):
    """The wire through a `TpuDecoder` on a hub session, `slab` bytes of
    fresh memory a write, as the pumps hand it over."""
    s = hub.register("s")
    dec = TpuDecoder(pipeline=s)
    got = []
    dec.on_digest(lambda kind, seq, d: got.append((kind, seq, d)))
    for a in range(0, len(wire), slab):
        assert dec.write(memoryview(np.frombuffer(wire[a:a + slab],
                                                  np.uint8).copy()))
    return s, dec, got


@pytest.mark.parametrize("case", ["wide", "small-among-changes"])
def test_the_copied_counter_says_which_blobs_the_hub_joined(case,
                                                            obs_enabled):
    """Wide blobs back to back park as views and nothing copies them
    ahead of the pack; a small blob in a slab of change frames would
    hold the slab for its few bytes, and is joined instead."""
    if case == "wide":
        blobs = [_blob(3000, i) for i in range(24)]
        wire = b"".join(frame(TYPE_BLOB, b) for b in blobs)
        copied = 0
    else:
        blobs = [_blob(16, i) for i in range(24)]
        wire = b"".join(frame(TYPE_CHANGE, _change(i)) * 20
                        + frame(TYPE_BLOB, b) for i, b in enumerate(blobs))
        copied = 16 * 24
    hub = ReplicationHub(linger_s=0.0)
    try:
        s, dec, got = _decode_through_hub(wire, 4096, hub)
        dec.end()
        assert dec.finished
    finally:
        hub.close()
    assert [d for kind, _, d in got if kind == "blob"] == \
        [_h(b) for b in blobs]
    counters = obs_enabled.REGISTRY.snapshot()["counters"]
    assert counters["decoder.blob.bytes"] == sum(map(len, blobs))
    assert counters.get("decoder.blob.copied.bytes", 0) == copied


# -- the budget is kept on what the views pin ----------------------------------


def _queued(session) -> tuple:
    """(bytes the queued items own, bytes of the distinct slabs their
    views pin) for a session of a parking hub."""
    owned, slabs = 0, {}
    for _kind, item, _cb, _tag, _nbytes in session._state.q:
        for piece in getattr(item, "parts", (item,)):
            if type(piece) is memoryview:
                slabs[id(piece.obj)] = memoryview(piece.obj).nbytes
            else:
                owned += len(piece)
    return owned, sum(slabs.values())


@pytest.mark.parametrize("blob_len", [16, 256, 3000, 9000])
@pytest.mark.parametrize("slab", [4096, 32768])
def test_a_sessions_parked_bytes_are_what_its_queue_pins(slab, blob_len):
    """Blobs between runs of change rows, nothing dispatched: whatever
    the mix of joined and parked-as-views, the session is charged the
    bytes its queue owns and every byte of every slab its views pin —
    but the tail of the newest, which is not known to be spare until
    the views move on."""
    wire = b"".join(
        frame(TYPE_CHANGE, _change(i)) * (1 + i % 37)
        + frame(TYPE_BLOB, _blob(blob_len, i)) for i in range(300))
    hub = _parking_hub()
    try:
        s = hub.register("s")
        dec = TpuDecoder(pipeline=s)
        dec.on_digest(lambda kind, seq, d: None)
        some_views = False
        for a in range(0, len(wire), slab):
            dec.write(memoryview(np.frombuffer(wire[a:a + slab],
                                               np.uint8).copy()))
            owned, pinned = _queued(s)
            _slab, _at, size, end = s._slab
            tail = size - end
            assert s._state.parked_bytes == owned + pinned - tail
            assert hub.snapshot()["parked_bytes"] == s._state.parked_bytes
            some_views = some_views or pinned > slab
        # the wider blobs did park as views of several slabs at once
        assert some_views is (blob_len >= 256)
        s.close()
        assert hub.snapshot()["parked_bytes"] == 0
    finally:
        hub.close()


def test_window_admission_and_shed_read_the_charged_bytes():
    """A 1,000-byte view at the head of a 30,000-byte slab pins 30,000
    bytes.  Counted by what it carries, three of them are 3,000 bytes and
    move nothing; charged by what they pin, the second shuts the
    session's window and the hub's admission, and the third is shed."""
    hub = _parking_hub(window_bytes=20_000, parked_budget=60_000)
    slabs = [np.zeros(30_000, np.uint8) for _ in range(3)]
    views = [memoryview(a)[HEADER:HEADER + 1000].toreadonly() for a in slabs]
    try:
        s = hub.register("s", nowait=True)
        s.submit_parts([views[0]], lambda d: None)
        assert s._state.parked_bytes == HEADER + 1000  # the tail: not yet
        assert s.window_room() and hub.admission_state()["open"]
        s.submit_parts([views[1]], lambda d: None)
        assert s._state.parked_bytes == 30_000 + HEADER + 1000
        assert not s.window_room()
        assert not hub.admission_state()["open"]
        with pytest.raises(HubBusy) as busy:
            hub.register("late")
        assert busy.value.parked_bytes == 30_000 + HEADER + 1000
        with pytest.raises(SessionShed) as shed:
            s.submit_parts([views[2]], lambda d: None)
        assert shed.value.parked_bytes == 60_000 + HEADER + 1000
        assert hub.snapshot()["parked_bytes"] == 0
    finally:
        hub.close()


# -- views die at the pack -----------------------------------------------------


class _Gated:
    """A batch's `collect` that waits at a gate and cannot say `ready`:
    the batch stays in flight, packed, for as long as the test likes."""

    def __init__(self, collect, gate):
        self._collect, self._gate = collect, gate

    def __call__(self):
        assert self._gate.wait(HARD_TIMEOUT)
        return self._collect()


@pytest.mark.parametrize("how", ["pack", "session-close", "hub-close",
                                 "shed"])
def test_no_slab_outlives_what_parked_it(how):
    """Weak references to the slabs: gone once the batch is packed (in
    flight, undelivered), once the session closes with work queued,
    once the hub closes under it, once it is shed — save the one slab
    the session's account remembers, until the session closes."""
    gate = threading.Event()
    hub = _parking_hub(parked_budget=20_000 if how == "shed" else 1 << 40)
    if how == "pack":
        begin = hub._pipeline._hash_begin
        hub._pipeline._hash_begin = lambda ps: _Gated(begin(ps), gate)
    blobs = [_blob(3000 + i, i) for i in range(12)]
    slabs, got = [], []
    try:
        s = hub.register("s", nowait=True)
        try:
            for i, b in enumerate(blobs):
                s.submit_parts(_views(b, [1000, 2000], keep=slabs),
                               lambda tag, d: got.append((tag, d)), i)
        except SessionShed:
            assert how == "shed"
        else:
            assert how != "shed"
        refs = [weakref.ref(a) for a in slabs]
        del slabs[:]

        def alive() -> int:
            gc.collect()
            return sum(r() is not None for r in refs)

        if how == "pack":
            assert alive() == len(refs)
            s.flush()  # nowait: the dispatcher's cue, not a wait
            _until(lambda: alive() <= 1, "the pack to drop the views")
            # packed, in the pipeline, undelivered: the gate is shut
            assert s._state.out_items == len(blobs) and got == []
        elif how == "hub-close":
            assert alive() == len(refs)
            hub.close()
        elif how == "session-close":
            assert alive() == len(refs)
        if how != "session-close":
            assert alive() == 1  # the account's newest slab
        gate.set()
        if how == "pack":
            def polled() -> bool:
                s.poll()
                return len(got) == len(blobs)

            _until(polled, "every digest")
            assert got == [(i, _h(b)) for i, b in enumerate(blobs)]
        s.close()
        assert alive() == 0
    finally:
        gate.set()
        hub.close()
