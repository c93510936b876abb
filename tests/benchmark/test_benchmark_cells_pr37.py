"""Per-layer metrics that read the program's readings of `digest.launch`
split into child spans and of the second clock, a thread's CPU seconds
beside a region's wall seconds, and one shared helper,
`layer_metrics/_shares.py`.  Five readers: the launch's one child (the
jit call, a mean in ms a batch) and the four off-CPU shares; the
slice's and the lengths' readers went with the spans they read, which
the one-chip launch no longer opens.  Each reader on hand-made
snapshots: a value where both clocks moved (the program takes the CPU
clock on one visit in a few, so a share compares the two MEANS);
nothing (the metric is then left out of the line) where the histograms
are absent, as in a program older than the spans; nothing where the
wall sum did not move or the CPU clock has too few observations;
nothing on a dark run.

The entries are in `BENCHMARK.json`, in `per_layer`.  The manifest case
below finds them BY NAME, holds each one's list of cells as a prefix and
pins no position, so that it holds on a manifest grown as a later PR
grows it (`test_benchmark_cells_pr40.py`)."""

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

CELLS = ["plain.publish", "edgehub.feed", "edgehub.publish",
         "meshhub.publish"]


EDGE = ["edgehub.feed", "edgehub.publish", "meshhub.publish"]
# name -> (unit, layer, the cells it lists; None: no list, every cell).
# A list where a reader finds nothing in some cell by design:
# `plain.publish` has no edge loop and 4-5 clocked batches a window
# (under `_shares.MIN_CLOCKED`).  The shares read on every edge cell, the
# four-chip one among them; the receive's also on `plain.publish`, whose
# read-ahead helper receives through the same `recv_fetch`
WANT = {
    "launch_program_ms": ("ms", "kernels", None),
    "launch_offcpu_share": ("%", "kernels", EDGE),
    "pack_offcpu_share": ("%", "staging", EDGE),
    "edge_read_offcpu_share": ("%", "host path", EDGE),
    "rx_offcpu_share": ("%", "host path", CELLS),
}
NAMES = list(WANT)


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _snap(t, hists):
    return {"monotonic": t, "metrics": {
        "counters": {}, "gauges": {},
        "histograms": {k: {"sum": s, "count": n}
                       for k, (s, n) in hists.items()}}}


def _ctx(first, last):
    return {"snaps": (_snap(100.0, first), _snap(140.0, last))}


def _pair(wall, cpu, first, last):
    """Both clocks of one region at the window's two ends:
    ``first``/``last`` = (wall sum, cpu sum, visits[, visits that took
    the CPU clock: all of them where not given])."""
    return _ctx({wall: (first[0], first[2]), cpu: (first[1], first[-1])},
                {wall: (last[0], last[2]), cpu: (last[1], last[-1])})


LAUNCH = ("span.digest.launch.seconds", "span.digest.launch.cpu_seconds")
PACK = ("span.digest.pack.seconds", "span.digest.pack.cpu_seconds")
READ = ("edge.turn.read_s", "edge.turn.read_cpu_s")
FETCH = ("pump.fetch.seconds", "pump.fetch.cpu_seconds")
# the parent: a lit program without the child spans and the second clock
PARENT = _ctx({"span.digest.launch.seconds": (1.0, 100),
               "span.digest.pack.seconds": (0.5, 100),
               "edge.turn.read_s": (3.0, 900)},
              {"span.digest.launch.seconds": (19.0, 2400),
               "span.digest.pack.seconds": (10.0, 2400),
               "edge.turn.read_s": (30.0, 4000)})
DARK = {"snaps": None}


@pytest.mark.parametrize("name, ctx, want", [
    # -- the three means: the window's sum over its count, in ms
    ("launch_program_ms",
     _ctx({"span.digest.launch.program.seconds": (0.5, 100)},
          {"span.digest.launch.program.seconds": (5.1, 2400)}), 2.0),
    ("launch_program_ms", PARENT, None),
    ("launch_program_ms", DARK, None),
    # -- the four shares: 100 x (1 - mean cpu / mean wall); every visit
    # clocked, the means compare as the sums do
    ("launch_offcpu_share",
     _pair(*LAUNCH, (1.0, 0.5, 100), (19.0, 5.0, 2400)), 75.0),
    ("pack_offcpu_share",
     _pair(*PACK, (0.5, 0.5, 100), (10.5, 8.5, 2400)), 20.0),
    ("edge_read_offcpu_share",
     _pair(*READ, (3.0, 2.0, 900), (30.0, 15.5, 4000)), 50.0),
    ("rx_offcpu_share",
     _pair(*FETCH, (2.0, 1.0, 5000), (22.0, 10.0, 30000)), 55.0),
    # the CPU clock on one visit in eight: the MEANS are compared —
    # 2,400 visits of 7.5 ms, 300 of them clocked at 3 ms of CPU
    ("launch_offcpu_share",
     _pair(*LAUNCH, (1.0, 0.05, 100, 13), (19.0, 0.95, 2500, 313)), 60.0),
    ("rx_offcpu_share",
     _pair(*FETCH, (2.0, 0.125, 5000, 625), (22.0, 1.125, 30000, 3750)),
     60.0),
    # fewer clocked visits than `_shares.MIN_CLOCKED` (plain.publish: 40
    # batches a window, five of them clocked, on 10 ms ticks): no reading
    ("pack_offcpu_share",
     _pair(*PACK, (0.5, 0.0, 100, 13), (0.9, 0.10, 140, 18)), None),
    ("launch_offcpu_share",
     _pair(*LAUNCH, (1.0, 0.05, 100, 13), (2.5, 0.17, 220, 28)), None),
    # ... and at the floor it is one: 16 clocked turns of 50 ms
    # (edgehub.feed's read phase clocks two dozen a window)
    ("edge_read_offcpu_share",
     _pair(*READ, (1.0, 0.1, 20, 3), (7.4, 0.74, 148, 19)), 20.0),
    # visits in the window, none of them clocked: nothing
    ("pack_offcpu_share",
     _pair(*PACK, (0.5, 0.3, 100, 13), (0.9, 0.3, 140, 13)), None),
    # mean CPU past mean wall (the clock's grain around a small true
    # share, or a broken clock) is let through, not clamped: to be seen
    ("pack_offcpu_share",
     _pair(*PACK, (0.5, 0.5, 100), (10.5, 11.5, 2400)), -10.0),
    # a thread that never left its CPU reads 0, and that IS a reading
    ("pack_offcpu_share",
     _pair(*PACK, (0.5, 0.5, 100), (10.5, 10.5, 2400)), 0.0),
    # the wall sum did not move (a path that makes no native receive
    # through `recv_fetch`, as `plain.publish` before its read-ahead
    # helper: the pair is registered at import and never fed): nothing,
    # not 0
    ("rx_offcpu_share",
     _pair(*FETCH, (0.0, 0.0, 0), (0.0, 0.0, 0)), None),
    ("edge_read_offcpu_share",
     _pair(*READ, (3.0, 2.0, 900), (3.0, 2.0, 900)), None),
    # one clock of the two: the parent has the wall histograms alone
    ("launch_offcpu_share", PARENT, None),
    ("pack_offcpu_share", PARENT, None),
    ("edge_read_offcpu_share", PARENT, None),
    ("rx_offcpu_share", PARENT, None),
    ("launch_offcpu_share", DARK, None),
    ("pack_offcpu_share", DARK, None),
    ("edge_read_offcpu_share", DARK, None),
    ("rx_offcpu_share", DARK, None),
])
def test_the_seven_readers_on_hand_made_snapshots(name, ctx, want):
    got = run.load_reader(name)(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_the_share_helper_is_no_metric_and_every_reader_is_a_file():
    for name in NAMES:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           f"{name}.py")), name
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       "_shares.py"))
    assert not [n for n in NAMES if n.startswith("_")]


def entry(name: str) -> dict:
    unit, layer, cells = WANT[name]
    e = {"name": name, "unit": unit, "better": "lower",
         "source": "program_counter", "layer": layer,
         "moves": "payload_rate"}
    if cells is not None:
        e["workloads"] = cells
    return e


def test_the_seven_entries_by_name_in_the_manifest():
    check_the_seven_entries(manifest())


def check_the_seven_entries(m: dict) -> None:
    """Each of the five once, found BY NAME, never by position: a pin
    on the list's order would bar the next PR, as a pin on its last
    entry once barred these.  Each has the contract's form, its list of
    cells begins with the cells it was entered with, it names a layer
    and an end-to-end metric the manifest has, and every accepted cell
    resolves exactly the readers that list it."""
    names = [p["name"] for p in m["per_layer"]]
    assert len(set(names)) == len(names)
    assert set(NAMES) <= set(names)
    by_name = {p["name"]: p for p in m["per_layer"]}
    layers = {p["layer"] for p in m["per_layer"] if p["name"] not in WANT}
    end_to_end = {e["name"] for e in m["end_to_end"]}
    for name, (_, layer, cells) in WANT.items():
        got = dict(by_name[name])
        want = entry(name)
        if cells is not None:
            assert got.pop("workloads")[:len(cells)] == want.pop("workloads")
        assert got == want
        assert layer in layers and "payload_rate" in end_to_end
    for cell in CELLS:
        plan = run.resolve(m, cell, dry=False)
        got = [mm["name"] for mm, _ in plan["per_layer"]]
        listed = [n for n, (_, _, cells) in WANT.items()
                  if cells is None or cell in cells]
        assert [n for n in got if n in WANT] == listed, cell
        assert all(callable(r) for _, r in plan["per_layer"])
