"""What ISSUE 36 adds to the benchmark: ONE per-layer metric,
`rx_fanned_share` — of the bytes the edge loop's read phase received in
the window, the share received on a helper thread beside its
neighbours' — read from two counters of the `--stats-fd` snapshot.  Its
reader on hand-made snapshots: a share where the counters moved, and
nothing (the metric is then left out of the line) where no byte moved
or where the program has no such counters, as the PR's parent has not.
No cell, no configuration, no file the benchmark had is changed."""

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
    m = manifest()
    assert m["per_layer"][-1] == {
        "name": "rx_fanned_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "host path",
        "moves": "payload_rate",
        "workloads": ["edgehub.feed", "edgehub.publish"]}
    assert [p["name"] for p in m["per_layer"]].count("rx_fanned_share") == 1
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       "rx_fanned_share.py"))


@pytest.mark.parametrize("cell, reports", [
    ("edgehub.feed", True), ("edgehub.publish", True),
    ("plain.publish", False), ("meshhub.publish", False)])
def test_the_cells_that_report_it(cell, reports):
    """The edge loop's cells on one chip; `plain.publish` has no edge
    loop, and `meshhub.publish` cannot be appended behind
    `edgehub.publish` until a `benchmark` PR rewrites the accepted case
    that holds it last (PERF.md section 7)."""
    m = manifest()
    names = [p["name"] for p in run.for_cell(m["per_layer"], cell)]
    assert ("rx_fanned_share" in names) is reports
