"""The cell ISSUE 35 adds — `meshhub.publish` (configuration
`meshhub1g` x traffic `publish8m`, four chips) — rehearsed on the CPU
and resolved by name; the accepted cells held first and in order in
every list of the manifest, whatever later PRs append; the traffic file
held to `publish8`'s mix; and the reader that came with the cell,
`mesh_hbm_share`, on hand-made snapshots and a hand-made reduced trace:
per chip, so four devices read a quarter of what one would, and nothing
where the program has no `hub.mesh.devices` gauge or ran no sharded
program, as the PR's parent has not.

The manifest cases are functions of a manifest dict, held on
`BENCHMARK.json` here and, by `test_benchmark_cells_pr40.py`, on a copy
grown as a later PR grows it: whatever is appended, they still hold."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
import run  # noqa: E402

sys.path.remove(BENCH)

CELL = "meshhub.publish"
ACCEPTED = ["plain.publish", "edgehub.feed", "edgehub.publish"]
MIX_KEYS = ["source", "generator", "loop", "item", "clients", "processes",
            "session_items", "pool_items", "trace_slice_s", "reduced",
            "dry_run"]


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_dry_run_of_the_new_cell():
    env = {k: v for k, v in os.environ.items() if not k.startswith("DAT_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000035", "--trace", "0", "--dry-run"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["dry_run"] is True and last["workload"] == CELL
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"] == last["attempted"] > 0
    assert last["sessions"] >= 8
    assert "0 sessions broke a guarantee" in r.stdout


def test_the_new_cell_resolves():
    check_the_new_cell_resolves(manifest())


def check_the_new_cell_resolves(m: dict) -> None:
    plan = run.resolve(m, CELL, dry=False)
    assert plan["cell"]["chips"] == 4 == plan["config"]["chips"]
    assert plan["config"]["name"] == "meshhub1g"
    assert plan["traffic"]["name"] == "publish8m"
    assert plan["config"]["sidecar_flags"] == [
        "--edge", "--hub", "--hub-mesh", "auto",
        "--hub-parked-budget", "1073741824"]
    assert plan["config"]["engine"] == "device-batch-mesh"
    assert plan["config"]["reference"] == "benchmarks/reference/digests.py"
    reported = {e["name"] for e in plan["end_to_end"]} \
        | {mm["name"] for mm, _ in plan["per_layer"]}
    assert "mesh_hbm_share" in reported
    # not its metrics: the pump is `plain`'s, `session_p95` has two
    # modes on this mix (PERF.md section 6), and the one-chip bandwidth
    # share would read four times too high on four chips
    assert not {"pump_busy", "session_p95", "blake2b_hbm_share"} & reported
    # every list-less metric, as in the accepted cells
    assert {"payload_rate", "digest_rate", "digest_lag_p95", "setup_s",
            "device_idle", "pallas_share", "compiles_in_window",
            "batch_items", "pad_share", "hbm_peak"} <= reported


def test_meshhub1g_is_edgehub1g_plus_one_flag():
    with open(os.path.join(BENCH, "configs", "edgehub1g.json")) as f:
        base = json.load(f)
    with open(os.path.join(BENCH, "configs", "meshhub1g.json")) as f:
        cfg = json.load(f)
    assert cfg["guarantees"] == base["guarantees"]
    assert len(cfg["guarantees"]) == 5
    flags = list(cfg["sidecar_flags"])
    at = flags.index("--hub-mesh")
    assert flags[at:at + 2] == ["--hub-mesh", "auto"]
    assert flags[:at] + flags[at + 2:] == base["sidecar_flags"]
    assert cfg["reduced"] == [] and cfg["reference"] == base["reference"]
    assert (cfg["chips"], cfg["engine"]) == (4, "device-batch-mesh")
    assert 1 <= len(cfg["source"]) <= 200
    assert {"parked_budget", "transport", "mesh_devices"} \
        <= set(cfg["assumed"])


def test_publish8m_is_publish8_with_a_shorter_loop_before_the_window():
    with open(os.path.join(BENCH, "traffic", "publish8.json")) as f:
        base = json.load(f)
    with open(os.path.join(BENCH, "traffic", "publish8m.json")) as f:
        mix = json.load(f)
    assert set(mix) == set(base) and mix["name"] == "publish8m"
    for key in MIX_KEYS:
        assert mix[key] == base[key], key
    assert mix["warmup"]["lone_sessions"] == base["warmup"]["lone_sessions"]
    assert 0 < mix["warmup"]["loop_seconds"] < base["warmup"]["loop_seconds"]
    assert set(mix["assumed"]) == set(base["assumed"])
    for key in set(base["assumed"]) - {"warmup"}:
        assert mix["assumed"][key] == base["assumed"][key], key


def test_the_accepted_cells_stay_first_in_every_list():
    check_the_accepted_cells_stay_first(manifest())


def check_the_accepted_cells_stay_first(m: dict) -> None:
    """Literal prefixes, true of this manifest and of any a later PR
    grows from it by appending: the accepted configurations and cells
    come first and in order, in `workloads` and in every metric's list
    of cells, and a new name comes after them."""
    assert [c["name"] for c in m["configs"]][:3] == \
        ["plain", "edgehub", "edgehub1g"]
    cells = [w["name"] for w in m["workloads"]]
    assert cells[:3] == ACCEPTED and CELL in cells[3:]
    for x in m["end_to_end"] + m["per_layer"]:
        lst = x.get("workloads")
        if lst is None:
            continue
        assert set(lst) <= set(cells), x["name"]
        was = [c for c in ACCEPTED if c in lst]
        assert lst[:len(was)] == was, x["name"]


def test_what_this_pr_added_to_the_manifest():
    check_what_this_pr_added(manifest())


def check_what_this_pr_added(m: dict) -> None:
    """Found by name; a list of cells is held as its accepted prefix,
    so that a later cell may be appended to it."""
    by_name = {p["name"]: p for p in m["per_layer"]}
    share = dict(by_name["mesh_hbm_share"])
    assert share.pop("workloads")[:1] == [CELL]
    assert share == {
        "name": "mesh_hbm_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "payload_rate"}
    # the one-chip reader finds nothing in the new cell: it keeps the
    # cells it had
    assert by_name["blake2b_hbm_share"]["workloads"][:3] == ACCEPTED
    assert CELL not in by_name["blake2b_hbm_share"]["workloads"]
    assert CELL not in by_name["pump_busy"]["workloads"]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("meshhub1g", "publish8m", 4)
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    assert CELL in four
    assert len(four) <= max(1, len(m["workloads"]) // 2)


def _snap(t, gauges=None, buckets=None):
    return {"monotonic": t, "blake2b_buckets": buckets or {},
            "metrics": {"counters": {}, "gauges": gauges or {},
                        "histograms": {}}}


MIB = 1 << 20
BW = 819e9


def _ctx(devices, programs, items=1400, dispatches=100):
    """A window of `dispatches` sharded dispatches of 14 MiB each, the
    slice holding the given program runs."""
    gauges = {} if devices is None else {"hub.mesh.devices": float(devices)}
    first = {"pallas:8192": {"dispatches": 10, "items": 140,
                             "padded_items": 1280}}
    last = {"pallas:8192": {"dispatches": 10 + dispatches,
                            "items": 140 + items,
                            "padded_items": 1280 + 128 * dispatches}}
    return {"snaps": (_snap(100.0, gauges, first),
                      _snap(140.0, gauges, last)),
            "window": {"ok_payload_bytes": 5000 * MIB, "ok_items": 5000},
            "trace": {"programs": programs, "window_s": 3.0, "busy_s": 2.0},
            "peaks": {"hbm_bytes_per_s": BW}}


SHARDED = {"jit_mesh_blake2b_words": [0.5, 50], "jit_other": [1.0, 7]}
ONE_CHIP = {"jit_blake2b_words_pallas": [0.5, 50]}
# 50 runs x 14 MiB over one chip's bandwidth, over 0.5 s of programs
ONE_DEVICE_WOULD_READ = 100.0 * (50 * 14 * MIB / BW) / 0.5


@pytest.mark.parametrize("ctx, want", [
    (_ctx(4, SHARDED), ONE_DEVICE_WOULD_READ / 4),
    (_ctx(1, SHARDED), ONE_DEVICE_WOULD_READ),
    (_ctx(2, {"jit_mesh_blake2b_words": [0.25, 25],
              "jit_mesh_blake2b_words_x": [0.25, 25]}),
     ONE_DEVICE_WOULD_READ / 2),
    # no gauge: the parent, or a hub without a mesh
    (_ctx(None, SHARDED), None),
    # the gauge, and no sharded program in the slice
    (_ctx(4, ONE_CHIP), None),
    (_ctx(4, {}), None),
    # dark, or a run without a trace
    ({**_ctx(4, SHARDED), "snaps": None}, None),
    ({**_ctx(4, SHARDED), "trace": None}, None),
], ids=["four-chips", "one-chip-mesh", "two-chips-two-programs",
        "no-gauge", "no-sharded-program", "no-program", "no-snapshots",
        "no-trace"])
def test_mesh_hbm_share_on_hand_made_runs(ctx, want):
    got = run.load_reader("mesh_hbm_share")(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want) and 0 < got < 100


def test_the_one_chip_reader_does_not_match_the_sharded_program():
    """Why the sharded program has a name of its own: the one-chip
    share divides ALL payload bytes by ONE chip's bandwidth."""
    assert run.load_reader("blake2b_hbm_share")(_ctx(4, SHARDED)) is None
    assert run.load_reader("mesh_hbm_share")(_ctx(4, ONE_CHIP)) is None
    assert run.load_reader("blake2b_hbm_share")(_ctx(4, ONE_CHIP)) \
        == pytest.approx(ONE_DEVICE_WOULD_READ)
