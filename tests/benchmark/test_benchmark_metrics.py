"""Metric arithmetic: the edge-to-edge rate, the per-item lag, the
nearest-rank percentile and the fewer-than-200 warning, as cases of one
parametrised test each; and the per-layer readers on hand-made
snapshots."""

import os
import sys

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks")
sys.path.insert(0, BENCH)
import metrics  # noqa: E402
from run import load_reader  # noqa: E402

sys.path.remove(BENCH)

MIB = 1 << 20


@pytest.mark.parametrize("values, q, want", [
    ([], 95, None),
    ([7.0], 95, 7.0),
    (list(range(1, 101)), 95, 95.0),       # rank ceil(0.95 * 100) = 95
    (list(range(1, 101)), 50, 50.0),
    (list(range(1, 21)), 95, 19.0),        # rank 19 of 20
    (list(range(1, 22)), 95, 20.0),        # rank ceil(19.95) = 20 of 21
    ([3.0, 1.0, 2.0], 100, 3.0),           # unsorted input
    ([3.0, 1.0, 2.0], 1, 1.0),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert metrics.percentile(values, q) == want


@pytest.mark.parametrize("n, warns", [
    (0, True), (199, True), (200, False), (5000, False)])
def test_a_p95_wants_ten_samples_beyond_it(n, warns):
    w = metrics.tail_warning("session_p95", n)
    assert (w is not None) == warns
    if warns:
        assert "200" in w and str(n) in w


@pytest.mark.parametrize("t, items, t0, t1, want_rate, want_span, n", [
    # 1,024 digests every 4 s: the first delivery is the edge, not work
    ([2, 6, 10, 14], [1024] * 4, 0, 16, 3 * 1024 / 12.0, 12.0, 4),
    # deliveries outside the window are not counted at all
    ([-1, 2, 6, 10, 17], [1024] * 5, 0, 16, 2 * 1024 / 8.0, 8.0, 3),
    # unsorted input from several processes
    ([10, 2, 6], [5, 5, 20], 0, 16, 25 / 8.0, 8.0, 3),
    # two deliveries at the first instant: both are the edge
    ([2, 2, 4], [7, 9, 10], 0, 16, 10 / 2.0, 2.0, 3),
    # a batch's digests split over recvs a millisecond apart are ONE
    # arrival: the rest of the first batch is not work after the edge
    ([2, 2.001, 2.002, 6, 6.001, 10], [400, 400, 224, 500, 524, 1024],
     0, 16, 2048 / 8.0, 8.0, 6),
    # ... but 100 ms later is a later arrival
    ([2, 2.1, 4], [7, 9, 10], 0, 16, 19 / 2.0, 2.0, 3),
    # one delivery: no rate, and the run fails on the missing metric
    ([3], [1024], 0, 16, None, None, 1),
    ([], [], 0, 16, None, None, 0),
])
def test_rates_run_edge_to_edge(t, items, t0, t1, want_rate, want_span, n):
    got = metrics.edge_to_edge(t, items, [i * MIB for i in items], t0, t1)
    assert got["deliveries"] == n
    assert got["span_s"] == want_span
    assert got["first_s"] == (min(x for x in t if t0 <= x <= t1) - t0
                              if n else None)
    if want_rate is None:
        assert got["items_per_s"] is None and got["bytes_per_s"] is None
    else:
        assert got["items_per_s"] == pytest.approx(want_rate)
        assert got["bytes_per_s"] == pytest.approx(want_rate * MIB)


def test_end_to_end_takes_lag_and_sessions_inside_the_window_only():
    timings = {
        "delivery_t": np.array([1.0, 2.0, 3.0, 9.0]),
        "delivery_items": np.array([10, 10, 10, 10]),
        "delivery_bytes": np.array([10, 10, 10, 10]) * MIB,
        # item times / lags in seconds; the one at t=9 is outside
        "item_t": np.array([1.0, 2.0, 3.0, 9.0]),
        "item_lag": np.array([0.1, 0.2, 0.3, 5.0]),
        "session_t0": np.array([0.5, 1.0, 8.0]),
        "session_t1": np.array([1.5, 3.0, 9.5]),
    }
    m, notes = metrics.end_to_end(timings, 0.0, 4.0)
    assert m["digest_rate"] == pytest.approx(10.0)
    assert m["payload_rate"] == pytest.approx(10.0)
    assert m["digest_lag_p95"] == pytest.approx(300.0)
    assert notes["digest_lag_p50_ms"] == pytest.approx(200.0)
    assert m["session_p95"] == pytest.approx(2000.0)
    assert notes["sessions_in_window"] == 2
    assert len(notes["warnings"]) == 2      # 3 lags, 2 sessions: under 200
    # a cell with one endless session has no session metric to report
    timings["session_t1"] = timings["session_t0"] = np.zeros(0)
    m, notes = metrics.end_to_end(timings, 0.0, 4.0)
    assert m["session_p95"] is None and len(notes["warnings"]) == 1


def _snap(t, buckets, traces=0, dispatch_sum=0.0, peak=None):
    return {"monotonic": t, "blake2b_buckets": buckets,
            "metrics": {"counters": {"device.jit.traces": traces},
                        "gauges": {"device.mem.peak_bytes_in_use": peak},
                        "histograms": {"decoder.dispatch.seconds":
                                       {"sum": dispatch_sum, "count": 1}}}}


CTX = {
    "snaps": (
        _snap(100.0, {"pallas:16": {"dispatches": 10, "items": 8000,
                                    "padded_items": 10240},
                      "xla-scan:16": {"dispatches": 4, "items": 40,
                                      "padded_items": 64}},
              traces=12, dispatch_sum=3.0, peak=1 << 30),
        _snap(110.0, {"pallas:16": {"dispatches": 30, "items": 28000,
                                    "padded_items": 30720},
                      "xla-scan:16": {"dispatches": 14, "items": 140,
                                      "padded_items": 224},
                      "xla-scan:8": {"dispatches": 0, "items": 0,
                                     "padded_items": 0}},
              traces=12, dispatch_sum=11.5, peak=3 << 29)),
    "window": {"ok_items": 1000, "ok_payload_bytes": 1000 * 1024,
               "sessions": 5},
    "cores": {"sidecar": 1.25, "client0": 0.25, "client1": 0.5},
    "trace": {"window_s": 3.0, "busy_s": 0.03,
              "programs": {"jit_blake2b_packed_pallas": [0.02, 4],
                           "jit_blake2b_packed": [0.005, 2],
                           "jit_other": [0.005, 1]}},
    "peaks": {"hbm_bytes_per_s": 819e9},
}
# over the window: 20,100 items in 30 dispatches, 20,640 padded slots of
# 16 blocks; 1,024-byte payloads in 2,048-byte slots


@pytest.mark.parametrize("name, want", [
    ("loadgen_cpu", 0.75),
    ("sidecar_cpu", 1.25),
    ("decode_busy", 0.85),
    ("batch_items", 20100 / 30),
    ("pad_share", 100 * (1 - 20100 * 1024 / (20640 * 16 * 128))),
    ("pallas_share", 100 * 20000 / 20100),
    ("blake2b_hbm_share",
     100 * (6 * (20100 * 1024 / 30) / 819e9) / 0.025),
    ("compiles_in_window", 0),
    ("device_idle", 99.0),
    ("hbm_peak", 1.5),
])
def test_layer_readers(name, want):
    assert load_reader(name)(CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "decode_busy", "batch_items", "pad_share", "pallas_share",
    "blake2b_hbm_share", "compiles_in_window", "device_idle", "hbm_peak"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    bare = {**CTX, "snaps": None, "trace": None}
    assert load_reader(name)(bare) is None
