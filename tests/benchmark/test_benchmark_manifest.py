"""BENCHMARK.json resolves: every name it gives is a file, every name and
unit is spelled from the allowed characters, and every per-layer metric
moves an end-to-end metric that each of its cells reports.

A configuration, a traffic mix, a cell and a per-layer metric are added
by new files plus manifest entries only: the last test does exactly that
in a copy of the tree and touches no file that was there.
"""

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load_run():
    sys.path.insert(0, BENCH)
    try:
        import run
    finally:
        sys.path.remove(BENCH)
    return run


def test_keys_and_limits_of_the_contract():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks", "tests/benchmark"]
    assert m["command"][1].startswith("benchmarks/")
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 2)


def test_names_units_and_lines_use_the_allowed_characters():
    m = manifest()
    names = []
    for c in m["configs"]:
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in m["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    metrics = m["end_to_end"] + m["per_layer"]
    for x in metrics:
        names.append(x["name"])
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    assert all(NAME.match(n) for n in names), names
    for group in (m["configs"], m["workloads"], metrics):
        seen = [x["name"] for x in group]
        assert len(seen) == len(set(seen))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    lines = [c["source"] for c in m["configs"]] \
        + [x["why"] for x in m["configs"] + m["workloads"]] \
        + [p["layer"] for p in m["per_layer"]] + m["command"]
    for s in lines:
        assert 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s, s


def test_every_name_resolves_to_a_file():
    m = manifest()
    run = load_run()
    used = set()
    for w in m["workloads"]:
        plan = run.resolve(m, w["name"], dry=False)
        used.add(w["config"])
        assert plan["config"]["name"] == w["config"]
        assert plan["config"]["chips"] == w["chips"]
        assert plan["config"]["guarantees"]
        assert plan["traffic"]["name"] == w["traffic"]
        assert "setup_s" in {e["name"] for e in plan["end_to_end"]}
        assert len(plan["end_to_end"]) >= 2 and plan["per_layer"]
        assert all(callable(read) for _, read in plan["per_layer"])
    assert used == {c["name"] for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    assert len(files) == len(set(files))
    for c in m["configs"]:
        assert c["file"].startswith("benchmarks/")
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert 1 <= len(body["source"]) <= 200
        assert body["reduced"] == c["reduced"]


def test_moves_names_an_end_to_end_metric_every_such_cell_reports():
    m = manifest()
    cells = [w["name"] for w in m["workloads"]]
    e2e = {e["name"]: e.get("workloads", cells) for e in m["end_to_end"]}
    layers = {}
    for p in m["per_layer"]:
        assert p["moves"] in e2e, p
        for cell in p.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[p["moves"]], (p["name"], cell)
        layers.setdefault(p["layer"].split(" (")[0], set()).add(p["layer"])
    # metrics of one layer give the same `layer`, letter for letter
    assert all(len(v) == 1 for v in layers.values()), layers


def test_missing_file_is_named(tmp_path):
    run = load_run()
    m = manifest()
    m["workloads"].append({"name": "plain.nothing", "config": "plain",
                           "traffic": "nothing", "chips": 1, "why": "x"})
    with pytest.raises(run.BenchFailure, match="traffic/nothing.json"):
        run.resolve(m, "plain.nothing", dry=False)
    with pytest.raises(run.BenchFailure, match="no workload 'absent'"):
        run.resolve(m, "absent", dry=False)
    m["per_layer"].append({"name": "no_reader", "unit": "x",
                           "better": "lower", "source": "host_clock",
                           "layer": "x", "moves": "digest_rate"})
    with pytest.raises(run.BenchFailure, match="layer_metrics/no_reader.py"):
        run.resolve(m, "plain.publish", dry=False)


def test_a_later_pr_adds_files_and_entries_and_edits_nothing(tmp_path):
    """A configuration, a mix, a cell (4 chips, flags of its own) and a
    per-layer metric, added to a copy of the tree as new files plus
    manifest entries; every file that was there keeps its bytes."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
              if p.is_file()}
    m = manifest()
    cfg = json.loads((root / "benchmarks/configs/edgehub.json").read_text())
    cfg.update(name="meshhub", chips=4, engine="mesh-sharded",
               sidecar_flags=["--edge", "--hub", "--hub-mesh", "auto"])
    (root / "benchmarks/configs/meshhub.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "benchmarks/traffic/feed.json").read_text())
    mix.update(name="trickle", clients=2, processes=1)
    (root / "benchmarks/traffic/trickle.json").write_text(json.dumps(mix))
    (root / "benchmarks/layer_metrics/sessions_served.py").write_text(
        "def read(ctx):\n    return float(ctx['window']['sessions'])\n")
    m["configs"].append({"name": "meshhub", "source": "x", "reduced": [],
                         "file": "benchmarks/configs/meshhub.json",
                         "why": "x"})
    m["workloads"].append({"name": "meshhub.trickle", "config": "meshhub",
                           "traffic": "trickle", "chips": 4, "why": "x"})
    m["per_layer"].append({
        "name": "sessions_served", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "front end",
        "moves": "digest_rate", "workloads": ["meshhub.trickle"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    sys.path.insert(0, str(root / "benchmarks"))
    saved = {k: sys.modules.pop(k) for k in ("run", "procs", "metrics")
             if k in sys.modules}
    try:
        import run
        assert run.ROOT == str(root)
        plan = run.resolve(m, "meshhub.trickle", dry=False)
    finally:
        sys.path.remove(str(root / "benchmarks"))
        for k in ("run", "procs", "metrics"):
            sys.modules.pop(k, None)
        sys.modules.update(saved)
    assert plan["cell"]["chips"] == 4
    assert plan["config"]["sidecar_flags"][-2:] == ["--hub-mesh", "auto"]
    assert plan["traffic"]["clients"] == 2
    names = [mm["name"] for mm, _ in plan["per_layer"]]
    assert "sessions_served" in names and "device_idle" in names
    read = dict((mm["name"], r) for mm, r in plan["per_layer"])
    assert read["sessions_served"]({"window": {"sessions": 7}}) == 7.0
    # the new metric is not reported by the old cells
    old = run.for_cell(m["per_layer"], "plain.publish")
    assert "sessions_served" not in [x["name"] for x in old]
    after = {p: p.read_bytes() for p in (root / "benchmarks").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {p: b for p, b in after.items() if p in before} == before
    assert len(after) == len(before) + 3
