"""Readiness-driven delivery (ISSUE 32): a batch's digests leave the
``DigestPipeline`` when its closure says the device has them, not inside
the dispatch of the second batch after it.

The in-flight bound stays what it was — backpressure — and ``flush()``
the barrier.  Under test, with a stub ``hash_begin`` whose closures'
``ready()`` the test flips: nothing is delivered while the oldest batch
is not ready; it is delivered at the next ``submit`` / ``submit_stream``
/ ``dispatch`` after it turns ready, oldest batch first, whole batches,
submit order kept; a closure with no probe keeps the old timing; the two
counters say which way a batch left; and the real CPU engine's closure
answers the probe and gives hashlib's digests (the mesh engine's:
``test_parallel_mesh.py``).
"""

import hashlib
import time

import pytest

from dat_replication_protocol_tpu.backend import tpu_backend
from dat_replication_protocol_tpu.backend.tpu_backend import (
    DigestPipeline,
    _HostStream,
)
from dat_replication_protocol_tpu.obs import metrics as obs_metrics
from dat_replication_protocol_tpu.ops.blake2b import blake2b_batch_begin


def _h(data: bytes) -> bytes:
    return hashlib.blake2b(data, digest_size=32).digest()


class _Engine:
    """A ``hash_begin`` that records what the pipeline asks of it.
    ``probe``: closures carry ``ready()``, answering ``self.ready[k]``
    for batch ``k`` (flipped by the test); without it they carry none."""

    def __init__(self, probe: bool = True):
        self.probe = probe
        self.ready: dict[int, bool] = {}
        self.probes = 0
        self.events: list[tuple] = []

    def __call__(self, payloads):
        k = len(self.ready)
        self.ready[k] = False
        self.events.append(("dispatch", k))
        payloads = list(payloads)

        def collect():
            self.events.append(("collect", k))
            return [_h(p) for p in payloads]

        if self.probe:
            def ready() -> bool:
                self.probes += 1
                return self.ready[k]

            collect.ready = ready
        return collect

    def collected(self) -> list[int]:
        return [k for what, k in self.events if what == "collect"]


@pytest.fixture
def unrationed(monkeypatch):
    """Every submit probes: the tests' submits are microseconds apart."""
    monkeypatch.setattr(tpu_backend, "_READY_PROBE_S", 0.0)


def _pipeline(engine, **kw) -> tuple[DigestPipeline, list]:
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_inflight", 2)
    return DigestPipeline(hash_begin=engine, **kw), []


def _fill(pipe, got, first: int, n: int) -> None:
    for i in range(first, first + n):
        pipe.submit(b"item-%d" % i, got.append)


def _want(*items: int) -> list[bytes]:
    return [_h(b"item-%d" % i) for i in items]


# -- the probe decides ---------------------------------------------------------

def test_nothing_is_delivered_while_the_oldest_is_not_ready(unrationed):
    engine = _Engine()
    pipe, got = _pipeline(engine, max_inflight=8)
    _fill(pipe, got, 0, 7)  # three batches in flight, a fourth filling
    assert pipe.inflight == 3 and got == []
    assert engine.probes >= 3  # it did ask
    assert engine.collected() == []


def _by_submit(pipe, got):
    pipe.submit(b"item-2", got.append)


def _by_stream(pipe, got):
    pipe.submit_stream(_HostStream().update(b"item-2"), got.append)


def _by_dispatch(pipe, got):
    pipe._polled = float("inf")  # no submit probes: the dispatch alone
    pipe.submit(b"item-2", got.append)
    assert got == []
    pipe.dispatch()


@pytest.mark.parametrize("notice", [_by_submit, _by_stream, _by_dispatch],
                         ids=["submit", "submit_stream", "dispatch"])
def test_delivered_at_the_next_entry_after_it_turns_ready(unrationed,
                                                          notice):
    engine = _Engine()
    pipe, got = _pipeline(engine, max_batch=2)
    _fill(pipe, got, 0, 2)  # batch 0 in flight
    assert pipe.inflight == 1 and got == []
    engine.ready[0] = True
    assert got == []  # turning ready delivers nothing by itself
    notice(pipe, got)
    assert got[:2] == _want(0, 1)
    assert engine.collected()[0] == 0


def test_oldest_first_and_whole_batches_only(unrationed):
    engine = _Engine()
    pipe, got = _pipeline(engine, max_inflight=8)
    _fill(pipe, got, 0, 4)  # batches 0 and 1 in flight
    engine.ready[1] = True  # the NEWER one is done first
    _fill(pipe, got, 4, 1)
    assert got == [] and pipe.inflight == 2  # it waits behind batch 0
    engine.ready[0] = True
    _fill(pipe, got, 5, 1)  # fills batch 2; its dispatch delivers too
    assert got == _want(0, 1, 2, 3)  # both, oldest first, submit order
    assert engine.collected() == [0, 1]
    assert pipe.inflight == 1  # batch 2: not ready, not forced
    pipe.flush()
    assert got == _want(0, 1, 2, 3, 4, 5)


def test_a_stream_entry_keeps_its_place_among_payloads(unrationed):
    engine = _Engine()
    pipe, got = _pipeline(engine, max_batch=3, max_inflight=8)
    pipe.submit(b"item-0", got.append, None)
    pipe.submit_stream(_HostStream().update(b"a stream"), got.append)
    pipe.submit(b"item-1", got.append)  # batch 0: payload, stream, payload
    pipe.submit_stream(_HostStream().update(b"another"), got.append)
    assert got == [] and pipe.inflight == 1
    engine.ready[0] = True
    pipe.submit(b"item-2", got.append)
    assert got == [_h(b"item-0"), _h(b"a stream"), _h(b"item-1")]
    pipe.flush()
    assert got[3:] == [_h(b"another"), _h(b"item-2")]


def test_tags_and_order_per_kind_across_ready_batches(unrationed):
    engine = _Engine()
    pipe, got = _pipeline(engine, max_batch=4, max_inflight=8)
    seqs = {"change": 0, "blob": 0}

    def emit(tag, digest):
        got.append(tag)

    for i in range(16):
        kind = "blob" if i % 3 == 0 else "change"
        pipe.submit(b"item-%d" % i, emit, (kind, seqs[kind]))
        seqs[kind] += 1
        if i == 9:
            engine.ready[0] = engine.ready[1] = True
    pipe.flush()
    for kind in seqs:
        assert [s for k, s in got if k == kind] == list(range(seqs[kind]))
    assert engine.collected() == [0, 1, 2, 3]


# -- a closure that cannot say, and one that never does: the old timing --------

@pytest.mark.parametrize("probe", [False, True],
                         ids=["no-probe", "never-ready"])
def test_the_inflight_bound_still_forces_and_nothing_comes_sooner(
        unrationed, probe):
    """What the pipeline did before it had a probe: a batch is handed
    over inside the dispatch of the second batch after it
    (``max_inflight`` 2), oldest first, and ``flush()`` drains the rest."""
    engine = _Engine(probe=probe)
    pipe, got = _pipeline(engine, max_batch=2, max_inflight=2)
    delivered_after = []
    for batch in range(4):
        _fill(pipe, got, 2 * batch, 2)
        delivered_after.append(len(got))
    assert delivered_after == [0, 0, 2, 4]
    assert engine.events == [
        ("dispatch", 0), ("dispatch", 1), ("dispatch", 2), ("collect", 0),
        ("dispatch", 3), ("collect", 1)]
    assert pipe.inflight == 2
    assert got == _want(0, 1, 2, 3)
    pipe.flush()
    assert got == _want(*range(8)) and pipe.inflight == 0
    assert (engine.probes > 0) == probe


@pytest.mark.parametrize("probe", [False, True], ids=["no-probe", "probe"])
def test_flush_delivers_everything_ready_or_not(unrationed, probe):
    engine = _Engine(probe=probe)
    pipe, got = _pipeline(engine, max_batch=2, max_inflight=8)
    _fill(pipe, got, 0, 5)
    engine.ready[1] = True
    pipe.flush()
    assert got == _want(0, 1, 2, 3, 4)
    assert engine.collected() == [0, 1, 2] and pipe.inflight == 0


# -- engines whose result exists at dispatch -----------------------------------

@pytest.mark.parametrize("kw", [
    {"hash_batch": lambda ps: [_h(bytes(p)) for p in ps]}, {}],
    ids=["hash_batch", "host-engine"])
def test_an_eager_engine_is_delivered_at_its_own_dispatch(kw):
    pipe = DigestPipeline(max_batch=2, max_inflight=2, **kw)
    got = []
    pipe.submit(b"item-0", got.append)
    assert got == []  # queued, nothing dispatched
    pipe.submit(b"item-1", got.append)
    assert got == _want(0, 1) and pipe.inflight == 0
    pipe.submit_stream(_HostStream().update(b"s"), got.append)
    pipe.submit_stream(_HostStream().update(b"t"), got.append)
    assert got[2:] == [_h(b"s"), _h(b"t")]  # a batch of streams alone


# -- the probe's price ----------------------------------------------------------

def test_a_run_of_submits_probes_about_once_a_millisecond():
    engine = _Engine()
    pipe, got = _pipeline(engine, max_batch=1 << 20, max_inflight=2)
    pipe.submit(b"first", got.append)
    pipe.dispatch()  # one batch in flight, never ready
    asked = engine.probes
    t0 = time.monotonic()
    for _ in range(1024):
        pipe.submit(b"x", got.append)
    elapsed = time.monotonic() - t0
    assert engine.probes - asked <= elapsed / tpu_backend._READY_PROBE_S + 1
    assert got == []


def test_no_probe_and_no_clock_where_nothing_is_in_flight(monkeypatch):
    def no_clock():
        raise AssertionError("submit read the clock with nothing in flight")

    monkeypatch.setattr(tpu_backend, "_monotonic", no_clock)
    engine = _Engine()
    pipe, got = _pipeline(engine, max_batch=1 << 20)
    for _ in range(8):
        pipe.submit(b"x", got.append)
    pipe.submit_stream(_HostStream().update(b"s"), got.append)
    assert engine.probes == 0 and pipe.inflight == 0


# -- the counters ----------------------------------------------------------------

def _deliver_counters() -> tuple[int, int]:
    counters = obs_metrics.snapshot()["counters"]
    return (counters.get("digest.deliver.ready", 0),
            counters.get("digest.deliver.forced", 0))


def test_the_two_counters_count_one_each_way(obs_enabled, unrationed):
    engine = _Engine()
    pipe, got = _pipeline(engine, max_batch=2, max_inflight=2)
    _fill(pipe, got, 0, 2)
    engine.ready[0] = True
    _fill(pipe, got, 2, 1)  # batch 0 leaves because it is ready
    assert _deliver_counters() == (1, 0)
    _fill(pipe, got, 3, 5)  # batches 1, 2, 3: the bound forces batch 1
    assert _deliver_counters() == (1, 1)
    pipe.flush()  # and the barrier the other two
    assert _deliver_counters() == (1, 3)
    assert got == _want(*range(8))


def test_dark_the_counters_stay_at_zero(unrationed):
    assert not obs_metrics.OBS.on
    engine = _Engine()
    pipe, got = _pipeline(engine)
    _fill(pipe, got, 0, 2)
    engine.ready[0] = True
    _fill(pipe, got, 2, 2)
    pipe.flush()
    assert got == _want(0, 1, 2, 3)
    assert _deliver_counters() == (0, 0)


# -- the real engines' closures ---------------------------------------------------

def _wait_ready(collect, seconds: float = 60.0) -> None:
    deadline = time.monotonic() + seconds
    while not collect.ready():
        assert time.monotonic() < deadline, "the batch never turned ready"
        time.sleep(0.001)


@pytest.mark.parametrize("sizes", [(0, 1, 127, 128, 129), (5000,) * 12,
                                   (3, 700, 3, 9000)],
                         ids=["block-edges", "one-bucket", "three-buckets"])
def test_cpu_engine_closure_reports_ready_and_matches_hashlib(sizes):
    payloads = [bytes([i + 1]) * n for i, n in enumerate(sizes)]
    collect = blake2b_batch_begin(payloads, use_pallas=False)
    assert isinstance(collect.ready(), bool)  # never blocks, never raises
    collect.start_d2h()
    _wait_ready(collect)
    assert collect() == [_h(p) for p in payloads]
    assert collect.ready()  # and stays so


def test_cpu_engine_through_the_pipeline_delivers_without_a_flush(
        unrationed):
    def begin(payloads):
        return blake2b_batch_begin(payloads, use_pallas=False)

    pipe = DigestPipeline(hash_begin=begin, max_batch=1 << 20,
                          max_inflight=2)
    got = []
    _fill(pipe, got, 0, 4)
    pipe.dispatch()  # batch 0 launched; no cap will close another
    deadline = time.monotonic() + 60.0
    while not got:
        assert time.monotonic() < deadline, "the batch never turned ready"
        time.sleep(0.001)
        # only the submit's probe can deliver: nothing dispatches
        pipe.submit_stream(_HostStream().update(b"tick"), lambda d: None)
    assert got == _want(0, 1, 2, 3) and pipe.dispatches == 1
    pipe.flush()
