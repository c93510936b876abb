"""The four-chip hub deployment (ISSUE 35, configuration `meshhub1g`)
at a small size, through the normal path: one `ReplicationHub(mesh=4)`
behind one `EdgeLoop`, as `sidecar --tcp --edge --hub --hub-mesh auto
--hub-parked-budget B` builds them on a four-chip host, 8 concurrent
raw-wire client sessions of 16 seeded blobs at the proportions of
`tests/test_hub_publishers.py` (a window of 15 blobs, the stated budget
32 windows).  The composed batches' rows are laid over 4 of the 8
virtual CPU devices (`tests/conftest.py`) by the served engine; every
reply is held against the benchmark's own plain reference
(`benchmarks/reference/digests.py`: hashlib and WIRE.md, nothing of the
package).
"""

import threading

import pytest
from test_hub_publishers import (
    BLOB,
    BLOBS,
    PINNED,
    PUBLISHERS,
    STATED_BUDGET,
    WINDOW,
    _faults,
    _join_all,
    _serve,
    _session,
    _start_publishers,
    _until,
)

from dat_replication_protocol_tpu.hub import ReplicationHub
from dat_replication_protocol_tpu.ops import blake2b

MESH = 4
NBLOCKS = BLOB // blake2b.BLOCK_BYTES


@pytest.mark.parametrize("seed", [35, 2147483683])
def test_eight_publishers_through_the_mesh_hub(obs_enabled, monkeypatch,
                                               seed):
    """The deployment: 8 sessions live at once on the mesh hub, nothing
    rejected, nothing shed, every one of the five guarantees held — one
    digest an item, submit order per kind, equal to BLAKE2b-256, the
    reply ended after the last digest (`_faults` reads each reply to its
    EOF) — and the instruments say which engine served: the served one,
    laid over 4 devices, handed every blob as the views it parked."""
    monkeypatch.setenv("DAT_DEVICE_HASH", "1")  # the CPU stands in
    hub = ReplicationHub(mesh=MESH, parked_budget=STATED_BUDGET,
                         window_bytes=WINDOW)
    assert hub.mesh_devices == MESH
    loop, port, t = _serve(hub, PUBLISHERS)
    traffic = [_session(seed, i) for i in range(PUBLISHERS)]
    replies: dict = {}
    hold = threading.Event()
    try:
        threads = _start_publishers(port, traffic, replies, hold)
        _until(lambda: loop.snapshot()["sessions"] == PUBLISHERS,
               "all 8 sessions live at once")
        assert hub.admission_state()["open"] is True
        hold.set()
        _join_all(threads, t)
        snap = obs_enabled.snapshot()
        from dat_replication_protocol_tpu.obs import device

        buckets = device.BUCKETS.snapshot()
    finally:
        hold.set()
        hub.close()
    for i in range(PUBLISHERS):
        assert _faults(replies[i], traffic[i][1]) == [], f"publisher {i}"
    counters, gauges = snap["counters"], snap["gauges"]
    assert counters["hub.admitted"] == PUBLISHERS
    assert counters["hub.rejected"] == 0 and counters["hub.shed"] == 0
    assert counters["hub.dispatch.items"] == PUBLISHERS * BLOBS
    assert gauges["hub.mesh.devices"] == MESH
    assert gauges["hub.parked.budget_bytes"] == STATED_BUDGET
    assert BLOB <= gauges["hub.parked.peak_bytes"] \
        <= PUBLISHERS * PINNED < STATED_BUDGET // 2
    # one bucket, the slot's: every row count a multiple of the mesh
    # times a declared per-chip count, and every item in it
    (key,) = buckets
    assert key == f"xla-scan:{NBLOCKS}"
    row = buckets[key]
    assert row["items"] == PUBLISHERS * BLOBS
    least = MESH * blake2b.declared_rows(NBLOCKS)[0]
    assert row["padded_items"] >= row["dispatches"] * least
    assert row["padded_items"] % least == 0
    # the pack was each blob's first copy: the hub joined none of them
    assert counters["decoder.blob.bytes"] == PUBLISHERS * BLOBS * BLOB
    assert counters["decoder.blob.copied.bytes"] == 0
    # the stage spans of the served engine, from the dispatcher thread
    for name in ("span.digest.pack.seconds", "span.digest.h2d.seconds",
                 "span.digest.launch.seconds",
                 "span.digest.d2h_wait.seconds",
                 "span.digest.unpack.seconds"):
        assert snap["histograms"][name]["count"] >= row["dispatches"], name
