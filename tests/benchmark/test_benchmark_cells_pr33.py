"""The cell ISSUE 33 adds — `edgehub.publish` (configuration
`edgehub1g` x traffic `publish8`) — rehearsed on the CPU and resolved by
name, and the three readers that came with it on hand-made `ctx` dicts:
each gives its value, and None (the metric is then left out of the line)
where the program has no such instrument, as the PR's parent has not.
`plain.feed` (`plain` x `feed`), asked for with it, is not in the
manifest: no test bars it, its tails spread past half their bound on
the chip (PERF.md section 7).  What is held here is that the cell it
would be still rehearses, so that a later PR adds two manifest entries
and no file.  The manifest cases are functions of a manifest dict, so
that `test_benchmark_cells_pr40.py` runs them on a copy grown as a later
PR grows it."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
import run  # noqa: E402

sys.path.remove(BENCH)

STAGE_READERS = ["idle_unattributed", "pack_busy", "h2d_busy",
                 "launch_busy", "collect_wait", "deliver_busy",
                 "batch_fill", "batch_residence"]
HUB_READERS = ["hub_busy", "edge_read_busy", "edge_drain_busy",
               "edge_tx_busy", "hub_parked_share", "batch_sessions",
               "blob_copies"]


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _with_plain_feed(tmp_path) -> str:
    """A copy of the benchmark with the queued cell's two manifest
    entries (a `workloads` entry and its name in `session_p95`'s list)
    and no new file."""
    m = manifest()
    m["workloads"].append({"name": "plain.feed", "config": "plain",
                           "traffic": "feed", "chips": 1, "why": "queued"})
    for e in m["end_to_end"]:
        if e["name"] == "session_p95":
            e["workloads"].append("plain.feed")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "dat_replication_protocol_tpu"),
               tmp_path / "dat_replication_protocol_tpu")
    return str(tmp_path)


@pytest.mark.parametrize("cell, sessions_at_least", [
    ("edgehub.publish", 8),
    ("plain.feed", 10),
])
def test_dry_run_of_the_new_cells(cell, sessions_at_least, tmp_path):
    root = REPO if cell == "edgehub.publish" else _with_plain_feed(tmp_path)
    env = {k: v for k, v in os.environ.items() if not k.startswith("DAT_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3000000033", "--trace", "0",
         "--dry-run"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["dry_run"] is True and last["workload"] == cell
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"] == last["attempted"] > 0
    assert last["sessions"] >= sessions_at_least
    assert "0 sessions broke a guarantee" in r.stdout


def test_the_new_cell_resolves():
    check_the_new_cell_resolves(manifest())


def check_the_new_cell_resolves(m: dict) -> None:
    plan = run.resolve(m, "edgehub.publish", dry=False)
    assert plan["cell"]["chips"] == 1 == plan["config"]["chips"]
    assert plan["config"]["name"] == "edgehub1g"
    assert plan["traffic"]["name"] == "publish8"
    assert plan["config"]["sidecar_flags"] == [
        "--edge", "--hub", "--hub-parked-budget", "1073741824"]
    assert plan["config"]["engine"] == "device-batch"
    assert plan["config"]["reference"] == "benchmarks/reference/digests.py"
    reported = {e["name"] for e in plan["end_to_end"]} \
        | {mm["name"] for mm, _ in plan["per_layer"]}
    assert set(STAGE_READERS + HUB_READERS) <= reported
    # not its metrics: the pump is `plain`'s; `session_p95` read two
    # modes over six seeds on the chip (PERF.md section 7)
    assert not {"pump_busy", "session_p95"} & reported
    # every list-less metric, as in the accepted cells
    assert {"payload_rate", "digest_rate", "digest_lag_p95", "setup_s",
            "device_idle", "blake2b_hbm_share", "pallas_share",
            "compiles_in_window", "batch_items", "pad_share"} <= reported


def test_edgehub1g_states_its_budget_and_weakens_no_guarantee():
    with open(os.path.join(BENCH, "configs", "edgehub.json")) as f:
        base = json.load(f)
    with open(os.path.join(BENCH, "configs", "edgehub1g.json")) as f:
        cfg = json.load(f)
    assert cfg["guarantees"] == base["guarantees"]
    assert len(cfg["guarantees"]) == 5
    assert cfg["sidecar_flags"][:2] == base["sidecar_flags"]
    assert cfg["sidecar_flags"][-2:] == ["--hub-parked-budget", "1073741824"]
    assert cfg["reduced"] == [] and cfg["reference"] == base["reference"]
    with open(os.path.join(BENCH, "traffic", "publish8.json")) as f:
        mix = json.load(f)
    # the budget's arithmetic: every publisher's full window plus the
    # blob that crosses it stays under the line where admission closes
    window = 32 << 20
    parked = mix["clients"] * (window + mix["item"]["bytes"])
    budget = int(cfg["sidecar_flags"][-1])
    assert parked == 264 << 20 and parked < budget // 2
    assert parked >= (256 << 20) // 2      # the default would refuse
    for sizes in (mix, {**mix, **mix["dry_run"]}):
        assert sizes["pool_items"] % sizes["session_items"] == 0
        assert sizes["clients"] >= sizes["processes"]


ACCEPTED = ["plain.publish", "edgehub.feed", "edgehub.publish",
            "meshhub.publish"]


def test_only_appended_cells_in_the_workloads_lists():
    check_only_appended_cells(manifest())


def check_only_appended_cells(m: dict) -> None:
    """The accepted cells come first, in their order, in `workloads`;
    in every metric's list the accepted cells it names come first, in
    that order.  Any name may follow them: a later cell is appended."""
    assert [w["name"] for w in m["workloads"]][:len(ACCEPTED)] == ACCEPTED
    for x in m["end_to_end"] + m["per_layer"]:
        cells = x.get("workloads")
        if cells is None:
            continue
        was = [c for c in ACCEPTED if c in cells]
        assert cells[:len(was)] == was, x["name"]


def _snap(t, counters=None, gauges=None, hists=None):
    return {"monotonic": t, "metrics": {
        "counters": counters or {}, "gauges": gauges or {},
        "histograms": {k: {"sum": s, "count": n}
                       for k, (s, n) in (hists or {}).items()}}}


def _ctx(first, last):
    return {"snaps": (_snap(100.0, **first), _snap(140.0, **last))}


GIB = 1 << 30
NO_INSTRUMENT = _ctx({}, {})     # the parent: a snapshot without them


@pytest.mark.parametrize("name, ctx, want", [
    ("hub_parked_share",
     _ctx({}, {"gauges": {"hub.parked.peak_bytes": 264.0 * (1 << 20),
                          "hub.parked.budget_bytes": float(GIB)}}),
     100.0 * 264 / 1024),
    # the budget is the collector's: a snapshot taken while no hub
    # lives has the registered peak gauge and no budget
    ("hub_parked_share",
     _ctx({}, {"gauges": {"hub.parked.peak_bytes": 0.0}}), None),
    ("hub_parked_share", NO_INSTRUMENT, None),
    ("hub_parked_share", {"snaps": None}, None),
    ("batch_sessions",
     _ctx({"hists": {"hub.dispatch.sessions": (70.0, 10)}},
          {"hists": {"hub.dispatch.sessions": (670.0, 110)}}), 6.0),
    # first lit batch inside the window
    ("batch_sessions",
     _ctx({}, {"hists": {"hub.dispatch.sessions": (24.0, 4)}}), 6.0),
    ("batch_sessions",
     _ctx({"hists": {"hub.dispatch.sessions": (70.0, 10)}},
          {"hists": {"hub.dispatch.sessions": (70.0, 10)}}), None),
    ("batch_sessions", NO_INSTRUMENT, None),
    ("batch_sessions", {"snaps": None}, None),
    ("blob_copies",
     _ctx({"counters": {"decoder.blob.bytes": GIB,
                        "decoder.blob.copied.bytes": GIB}},
          {"counters": {"decoder.blob.bytes": 41 * GIB,
                        "decoder.blob.copied.bytes": 41 * GIB}}), 1.0),
    ("blob_copies",
     _ctx({"counters": {"decoder.blob.bytes": GIB,
                        "decoder.blob.copied.bytes": 0}},
          {"counters": {"decoder.blob.bytes": 41 * GIB,
                        "decoder.blob.copied.bytes": 0}}), 0.0),
    # no blob byte moved (a feed of changes): None, not a division by 0
    ("blob_copies",
     _ctx({"counters": {"decoder.blob.bytes": 0,
                        "decoder.blob.copied.bytes": 0}},
          {"counters": {"decoder.blob.bytes": 0,
                        "decoder.blob.copied.bytes": 0}}), None),
    ("blob_copies",
     _ctx({}, {"counters": {"decoder.blob.bytes": GIB}}), None),
    ("blob_copies", NO_INSTRUMENT, None),
    ("blob_copies", {"snaps": None}, None),
])
def test_the_new_readers_on_hand_made_snapshots(name, ctx, want):
    got = run.load_reader(name)(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
