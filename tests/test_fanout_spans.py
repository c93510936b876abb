"""The fan-out's two stage spans and its byte-exact relay to many
socket peers.

* ``FanoutServer`` with N socketpair peers, fed slabs of seeded random
  sizes, hands every peer exactly the bytes published — the reference
  is their plain concatenation — at 1, 4 and 16 peers;
* ``fanout.dispatch`` (the dispatcher's turn, on ``fanout-dispatch``)
  and ``fanout.publish`` (the source's tap, on the publishing thread)
  are ``utils.trace.span`` stage spans: lit, each visit is one obs-ring
  record and one ``span.<name>.seconds`` observation; dark, each site
  is the bare profiler annotation and nothing else.
"""

from __future__ import annotations

import random
import socket
import threading

import pytest

from dat_replication_protocol_tpu.fanout import FanoutServer
from dat_replication_protocol_tpu.obs import metrics as obs_metrics
from dat_replication_protocol_tpu.obs.tracing import SPANS
from dat_replication_protocol_tpu.utils import trace as trace_mod

SPANS_OF_FANOUT = ("fanout.dispatch", "fanout.publish")


def _slabs(seed: int, total: int) -> list[bytes]:
    """Seeded slabs of 1 B to 256 KiB, about `total` bytes in all."""
    rng = random.Random(seed)
    out, n = [], 0
    while n < total:
        size = rng.randint(1, 256 << 10)
        out.append(rng.randbytes(size))
        n += size
    return out


class _Reader:
    """One socketpair peer: the fan-out writes one end, a thread reads
    the other to EOF."""

    def __init__(self):
        self.ours, self.theirs = socket.socketpair()
        self.got = bytearray()
        self.thread = threading.Thread(target=self._read, daemon=True)
        self.thread.start()

    def _read(self) -> None:
        while True:
            data = self.theirs.recv(1 << 16)
            if not data:
                return
            self.got += data

    def close(self) -> None:
        self.ours.close()
        self.thread.join(10)
        self.theirs.close()


def _relay(peers: int, slabs: list[bytes]) -> list[bytes]:
    """Publish `slabs` to `peers` socket peers; what each received."""
    srv = FanoutServer(stall_timeout=10.0)
    readers = [_Reader() for _ in range(peers)]
    try:
        handles = [srv.attach_peer(f"p{i}", fd=r.ours.fileno())
                   for i, r in enumerate(readers)]
        for slab in slabs:
            srv.publish(memoryview(slab))
        srv.seal()
        assert srv.drain(30)
        for h in handles:
            assert h.wait_done(5) and h.shed_reason is None
            h.close()
    finally:
        srv.close()
        for r in readers:
            r.close()
    return [bytes(r.got) for r in readers]


@pytest.mark.parametrize("peers", [1, 4, 16])
@pytest.mark.parametrize("seed", [7, 3000000044])
def test_every_peer_receives_exactly_the_published_bytes(peers, seed):
    slabs = _slabs(seed, 2 << 20)
    want = b"".join(slabs)
    got = _relay(peers, slabs)
    assert [len(g) for g in got] == [len(want)] * peers
    assert all(g == want for g in got)


def _span_counts() -> dict:
    hists = obs_metrics.snapshot()["histograms"]
    return {name: hists.get(f"span.{name}.seconds", {}).get("count", 0)
            for name in SPANS_OF_FANOUT}


def test_both_spans_are_observed_when_lit(obs_enabled):
    slabs = _slabs(11, 512 << 10)
    got = _relay(3, slabs)
    assert all(g == b"".join(slabs) for g in got)
    counts = _span_counts()
    # one publish span a slab; at least one dispatcher turn served them
    assert counts["fanout.publish"] == len(slabs)
    assert counts["fanout.dispatch"] >= 1
    published = [r["fields"] for r in SPANS.spans("fanout.publish")]
    assert [f["bytes"] for f in published] == [len(s) for s in slabs]
    turns = [r["fields"] for r in SPANS.spans("fanout.dispatch")]
    assert turns and all(f["src"] == "jax" for f in turns + published)
    assert all(1 <= f["peers"] <= 3 for f in turns)


def test_both_spans_are_bare_annotations_when_dark(monkeypatch):
    """Gate off: each site makes the bound factory's object and nothing
    else — no ring record, no histogram observation."""
    assert not obs_metrics.OBS.on
    made: list = []

    class Bare:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_span_factory", Bare)
    before_spans = len(SPANS.spans())
    before = _span_counts()
    slabs = _slabs(13, 256 << 10)
    assert all(g == b"".join(slabs) for g in _relay(2, slabs))
    assert made.count("fanout.publish") == len(slabs)
    assert made.count("fanout.dispatch") >= 1
    assert len(SPANS.spans()) == before_spans
    assert _span_counts() == before
