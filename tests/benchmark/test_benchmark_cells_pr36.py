"""What ISSUE 36 adds to the benchmark: ONE per-layer metric,
`rx_fanned_share` — of the bytes the edge loop's read phase received in
the window, the share received on a helper thread beside its
neighbours' — read from two counters of the `--stats-fd` snapshot.  Its
reader on hand-made snapshots: a share where the counters moved, and
nothing (the metric is then left out of the line) where no byte moved
or where the program has no such counters, as the PR's parent has not.
No cell, no configuration, no file the benchmark had is changed.  The
manifest cases find the entry by name and hold its list of cells as a
prefix, so that a later entry or cell is appended behind them
(`test_benchmark_cells_pr40.py` runs them on a manifest grown so)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
import run  # noqa: E402

sys.path.remove(BENCH)

GIB = 1 << 30


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _snap(t, counters=None):
    return {"monotonic": t, "metrics": {
        "counters": counters or {}, "gauges": {}, "histograms": {}}}


def _ctx(first, last):
    return {"snaps": (_snap(100.0, first), _snap(140.0, last))}


@pytest.mark.parametrize("ctx, want", [
    # eight publishers: nearly every byte received on a helper
    (_ctx({"edge.rx.bytes": 2 * GIB, "edge.rx.fanned.bytes": GIB},
          {"edge.rx.bytes": 42 * GIB, "edge.rx.fanned.bytes": 39 * GIB}),
     95.0),
    # a feed of small reads: the loop received, nothing was handed out
    (_ctx({"edge.rx.bytes": GIB, "edge.rx.fanned.bytes": 0},
          {"edge.rx.bytes": 3 * GIB, "edge.rx.fanned.bytes": 0}), 0.0),
    # the first lit turn fell inside the window
    (_ctx({}, {"edge.rx.bytes": 4 * GIB, "edge.rx.fanned.bytes": GIB}),
     25.0),
    # no byte moved: nothing, not a division by zero
    (_ctx({"edge.rx.bytes": GIB, "edge.rx.fanned.bytes": GIB},
          {"edge.rx.bytes": GIB, "edge.rx.fanned.bytes": GIB}), None),
    # one counter of the two (no such program, but a reader never raises)
    (_ctx({}, {"edge.rx.bytes": GIB}), None),
    # the parent: snapshots without the counters
    (_ctx({}, {}), None),
    (_ctx({"decoder.blob.bytes": GIB}, {"decoder.blob.bytes": 41 * GIB}),
     None),
    # a dark run has no snapshots
    ({"snaps": None}, None),
])
def test_rx_fanned_share_on_hand_made_snapshots(ctx, want):
    got = run.load_reader("rx_fanned_share")(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_what_this_pr_added_to_the_manifest():
    check_what_this_pr_added(manifest())


def check_what_this_pr_added(m: dict) -> None:
    entries = [p for p in m["per_layer"] if p["name"] == "rx_fanned_share"]
    assert len(entries) == 1
    entry = dict(entries[0])
    assert entry.pop("workloads")[:2] == ["edgehub.feed", "edgehub.publish"]
    assert entry == {
        "name": "rx_fanned_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "host path",
        "moves": "payload_rate"}
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       "rx_fanned_share.py"))


REPORTS = [("edgehub.feed", True), ("edgehub.publish", True),
           ("plain.publish", False), ("meshhub.publish", True)]


@pytest.mark.parametrize("cell, reports", REPORTS)
def test_the_cells_that_report_it(cell, reports):
    check_the_cells_that_report_it(manifest(), cell, reports)


def check_the_cells_that_report_it(m: dict, cell: str,
                                   reports: bool) -> None:
    """The edge loop's cells, on one chip and over the mesh (the same
    loop receives on its helpers there); `plain.publish` has no edge
    loop."""
    names = [p["name"] for p in run.for_cell(m["per_layer"], cell)]
    assert ("rx_fanned_share" in names) is reports
