"""The stage readers (ISSUE 26) on a hand-made `ctx`: each reads its
histogram across the window (last snapshot minus first) or the
reduction's `idle_gaps`, and each returns None — the metric is then left
out of the line — where the program has no such histogram (the parent of
the PR that added the spans) or the run has no trace."""

import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks")
sys.path.insert(0, BENCH)
from run import load_reader  # noqa: E402

sys.path.remove(BENCH)


def _snap(t, hists):
    return {"monotonic": t, "metrics": {
        "counters": {}, "gauges": {},
        "histograms": {k: {"sum": s, "count": n}
                       for k, (s, n) in hists.items()}}}


# a 10 s window; (sum, count) of each histogram at its two ends
FIRST = {
    "span.digest.pack.seconds": (2.0, 4),
    "span.digest.h2d.seconds": (1.0, 4),
    "span.digest.launch.seconds": (0.5, 4),
    "span.digest.d2h_wait.seconds": (0.25, 4),
    "span.digest.deliver.seconds": (0.125, 4),
    "span.pump.recv.seconds": (10.0, 100),
    "digest.batch.fill_s": (8.0, 4),
    "digest.batch.residence_s": (16.0, 4),
    "hub.dispatch.latency": (3.0, 90),
    "edge.turn.read_s": (4.0, 1000),
    "edge.turn.hub_drain_s": (1.0, 500),
    # `edge.turn.tx_s` first moves inside the window: absent here
}
LAST = {
    "span.digest.pack.seconds": (7.0, 6),
    "span.digest.h2d.seconds": (3.0, 6),
    "span.digest.launch.seconds": (0.75, 6),
    "span.digest.d2h_wait.seconds": (0.25, 6),
    "span.digest.deliver.seconds": (0.625, 6),
    "span.pump.recv.seconds": (13.5, 400),
    "digest.batch.fill_s": (16.5, 6),
    "digest.batch.residence_s": (33.0, 6),
    "hub.dispatch.latency": (10.5, 500),
    "edge.turn.read_s": (8.5, 3000),
    "edge.turn.hub_drain_s": (2.25, 900),
    "edge.turn.tx_s": (1.5, 700),
}
CTX = {
    "snaps": (_snap(100.0, FIRST), _snap(110.0, LAST)),
    # as `reduce_trace.reduce` gives them: rows of [name, seconds]
    "trace": {"window_s": 6.0, "busy_s": 0.5,
              "idle_gaps": [["pump.recv", 3.0], ["digest.pack", 1.4],
                            ["no span", 0.55], ["decode.write", 0.55]]},
}
# a program without the stage spans (the parent): the histograms that
# were there before are there, the trace has its old rows
PARENT = {
    "snaps": (_snap(100.0, {}), _snap(110.0, {})),
    "trace": None,
}


@pytest.mark.parametrize("name, want", [
    ("idle_unattributed", 100 * 0.55 / 5.5),
    ("pack_busy", 0.5),
    ("h2d_busy", 0.2),
    ("launch_busy", 0.025),
    ("collect_wait", 0.0),
    ("deliver_busy", 0.05),
    ("batch_fill", 4250.0),          # 8.5 s over 2 batches
    ("batch_residence", 8500.0),
    ("pump_busy", 0.35),
    ("hub_busy", 0.75),
    ("edge_read_busy", 0.45),
    ("edge_drain_busy", 0.125),
    ("edge_tx_busy", 0.15),          # absent at the first end: from 0
])
def test_stage_readers(name, want):
    read = load_reader(name)
    assert read(CTX) == pytest.approx(want)
    assert read(PARENT) is None
    assert read({"snaps": None, "trace": None}) is None


def test_a_trace_where_every_gap_has_a_name_reads_zero_unattributed():
    ctx = {"trace": {"window_s": 3.0, "busy_s": 0.0,
                     "idle_gaps": [["hub.submit", 3.0]]}}
    assert load_reader("idle_unattributed")(ctx) == 0.0


def test_a_mean_with_no_observation_in_the_window_reads_nothing():
    same = {"digest.batch.fill_s": (8.0, 4)}
    ctx = {"snaps": (_snap(1.0, same), _snap(2.0, same))}
    assert load_reader("batch_fill")(ctx) is None
