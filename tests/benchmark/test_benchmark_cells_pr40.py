"""What the benchmark holds once no test pins a manifest entry's
position: the four-chip cell `meshhub.publish` listed by the stage and
hub readers and by `rx_fanned_share`, the seven readers of the split
launch and the second clock entered, and one more reader, `pump_wait` —
the session thread's wait for its read-ahead helper `pump-rx-0`, beside
`pump_busy` — on hand-made snapshots.

And a rehearsal that keeps the next PR unblocked: every accepted
manifest case, run on a copy of the benchmark grown as a later PR grows
it — a fifth configuration with its file, a one-chip cell on it
appended to `workloads` and to every list of cells, and a per-layer
metric with its reader appended last.  The cases of the `pr3x` files
are functions of a manifest dict; those of
`test_benchmark_manifest.py` read the tree through its module names,
which the rehearsal points at the copy."""

import copy
import importlib.util
import inspect
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, HERE)
import test_benchmark_cells_pr33 as pr33  # noqa: E402
import test_benchmark_cells_pr35 as pr35  # noqa: E402
import test_benchmark_cells_pr36 as pr36  # noqa: E402
import test_benchmark_cells_pr37 as pr37  # noqa: E402
import test_benchmark_manifest as manifest_cases  # noqa: E402

sys.path.remove(HERE)
run = pr33.run

# the stage and hub readers whose lists name every cell on the served
# digest path's staging and dispatcher, the four-chip one among them
STAGE_AND_HUB = pr33.STAGE_READERS + pr33.HUB_READERS
PUMP_WAIT = {"name": "pump_wait", "unit": "s/s", "better": "lower",
             "source": "program_counter", "layer": "host path",
             "moves": "payload_rate"}


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


WAIT = "span.pump.wait.seconds"


@pytest.mark.parametrize("ctx, want", [
    # 14 s of waiting over the 40 s window
    (_ctx({WAIT: (2.0, 1000)}, {WAIT: (16.0, 45000)}), 0.35),
    # the first wait fell inside the window: from zero
    (_ctx({}, {WAIT: (4.0, 9000)}), 0.1),
    # registered, no wait in the window (a connection that stayed
    # inline): nothing, not 0
    (_ctx({WAIT: (2.0, 1000)}, {WAIT: (2.0, 1000)}), None),
    # the parent: snapshots without the span
    (_ctx({}, {}), None),
    (_ctx({"span.pump.recv.seconds": (1.0, 500)},
          {"span.pump.recv.seconds": (9.0, 4500)}), None),
    # a dark run has no snapshots
    ({"snaps": None}, None),
])
def test_pump_wait_on_hand_made_snapshots(ctx, want):
    got = run.load_reader("pump_wait")(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_what_this_pr_added_to_the_manifest():
    check_what_this_pr_added(manifest())


def check_what_this_pr_added(m: dict) -> None:
    """By name; each list of cells held as the prefix it was entered
    with."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in STAGE_AND_HUB + ["rx_fanned_share"]:
        cells = by_name[name]["workloads"]
        assert "meshhub.publish" in cells, name
        assert cells[:cells.index("meshhub.publish")] == [
            c for c in pr33.ACCEPTED[:3] if c in cells], name
    assert "meshhub.publish" not in by_name["pump_busy"]["workloads"]
    wait = dict(by_name["pump_wait"])
    assert wait.pop("workloads")[:1] == ["plain.publish"]
    assert wait == PUMP_WAIT
    assert set(pr37.NAMES) <= set(by_name)
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                       "pump_wait.py"))


@pytest.mark.parametrize("cell, reports", [
    ("plain.publish", True), ("edgehub.feed", False),
    ("edgehub.publish", False), ("meshhub.publish", False)])
def test_the_cells_that_report_pump_wait(cell, reports):
    """The thread-per-connection leg's cell: the edge cells receive on
    the loop's own helpers, and have no session thread to wait."""
    names = [p["name"] for p in run.for_cell(manifest()["per_layer"], cell)]
    assert ("pump_wait" in names) is reports


# -- the rehearsal ----------------------------------------------------------

LATER_CONFIG = "later"
LATER_CELL = "later.feed"
LATER_METRIC = "later_busy"


def _load_run(bench: str):
    """The copy's `run.py`, whose ROOT is the copy, under a name of its
    own; the paths it puts on `sys.path` are taken off again."""
    spec = importlib.util.spec_from_file_location(
        "run_of_the_grown_copy", os.path.join(bench, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    path = list(sys.path)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path[:] = path
    return mod


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """(manifest, its `run` module, root) of a copy of the benchmark
    grown by new files and appended entries alone."""
    root = tmp_path_factory.mktemp("grown")
    bench = root / "benchmarks"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = manifest()
    cfg = json.loads((bench / "configs" / "plain.json").read_text())
    cfg["name"] = LATER_CONFIG
    (bench / "configs" / f"{LATER_CONFIG}.json").write_text(json.dumps(cfg))
    (bench / "layer_metrics" / f"{LATER_METRIC}.py").write_text(
        "def read(ctx):\n    return None\n")
    m["configs"].append({
        "name": LATER_CONFIG, "source": "a rehearsal", "reduced": [],
        "file": f"benchmarks/configs/{LATER_CONFIG}.json",
        "why": "a rehearsal"})
    m["workloads"].append({"name": LATER_CELL, "config": LATER_CONFIG,
                           "traffic": "feed", "chips": 1,
                           "why": "a rehearsal"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "workloads" in x:
            x["workloads"].append(LATER_CELL)
    m["per_layer"].append({
        "name": LATER_METRIC, "unit": "s/s", "better": "lower",
        "source": "program_counter", "layer": "decode",
        "moves": "payload_rate", "workloads": [LATER_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(m, indent=1))
    return m, _load_run(str(bench)), root


MODULE_CASES = [
    (pr33, "check_the_new_cell_resolves", ()),
    (pr33, "check_only_appended_cells", ()),
    (pr35, "check_the_new_cell_resolves", ()),
    (pr35, "check_the_accepted_cells_stay_first", ()),
    (pr35, "check_what_this_pr_added", ()),
    (pr36, "check_what_this_pr_added", ()),
    *[(pr36, "check_the_cells_that_report_it", case)
      for case in pr36.REPORTS],
    (pr37, "check_the_seven_entries", ()),
    (sys.modules[__name__], "check_what_this_pr_added", ()),
]


@pytest.mark.parametrize(
    "module, name, args", MODULE_CASES,
    ids=[f"{mod.__name__.rsplit('_', 1)[-1]}-{name}"
         + "".join(f"-{a}" for a in args)
         for mod, name, args in MODULE_CASES])
def test_the_accepted_cases_hold_on_a_grown_copy(module, name, args,
                                                 grown, monkeypatch):
    m, grown_run, _ = grown
    assert LATER_CELL in [w["name"] for w in m["workloads"]]
    monkeypatch.setattr(module, "run", grown_run)
    getattr(module, name)(copy.deepcopy(m), *args)


MANIFEST_CASES = sorted(n for n in vars(manifest_cases)
                        if n.startswith("test_"))


@pytest.mark.parametrize("name", MANIFEST_CASES)
def test_the_manifest_cases_hold_on_a_grown_copy(name, grown, monkeypatch,
                                                 tmp_path):
    m, grown_run, root = grown
    monkeypatch.setattr(manifest_cases, "REPO", str(root))
    monkeypatch.setattr(manifest_cases, "BENCH", str(root / "benchmarks"))
    monkeypatch.setattr(manifest_cases, "manifest",
                        lambda: copy.deepcopy(m))
    monkeypatch.setattr(manifest_cases, "load_run", lambda: grown_run)
    case = getattr(manifest_cases, name)
    params = inspect.signature(case).parameters
    case(**({"tmp_path": tmp_path} if "tmp_path" in params else {}))
