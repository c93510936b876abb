"""The deployment `fanoutfeed` and its cell `fanout.follow` (traffic
`follow16`): one writer's YCSB-A change feed, digested once by the
plain served path and relayed live by `sidecar --fanout` to 16 readers.
Held here: the manifest's new entries, found by name, with the accepted
cells as a literal prefix of every list (also on a copy grown as a
later PR grows it); the traffic file against the mix it was sized by;
the three fan-out readers on hand-made snapshots; the cell's dry run;
and a lit rehearsal on the CPU in which the three readers read the
sidecar's own snapshots."""

import copy
import json
import os
import subprocess
import sys
import tempfile

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, HERE)
import test_benchmark_cells_pr33 as pr33  # noqa: E402

sys.path.remove(HERE)
run = pr33.run

CELL = "fanout.follow"
CONFIG = "fanoutfeed"
TRAFFIC = "follow16"
ACCEPTED_CONFIGS = ["plain", "edgehub", "edgehub1g", "meshhub1g",
                    "plainfolder"]
ACCEPTED_CELLS = pr33.ACCEPTED + ["plain.objects"]
# the accepted readers that read the writer's path: its stages, its pump
# and read-ahead, and the kernel's bandwidth share (uniform ~1 KB rows,
# nothing streamed on the host)
LISTED = pr33.STAGE_READERS + ["pump_busy", "pump_wait", "rx_offcpu_share",
                               "blake2b_hbm_share"]
LAYER = {"source": "program_counter", "layer": "fan-out"}
NEW_METRICS = {
    "fanout_dispatch_busy": {"unit": "s/s", "better": "lower",
                             "moves": "digest_rate", **LAYER},
    "fanout_frame_lag_ms": {"unit": "ms", "better": "lower",
                            "moves": "digest_lag_p95", **LAYER},
    "fanout_push_kib": {"unit": "KiB", "better": "higher",
                        "moves": "digest_rate", **LAYER},
}


def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


# -- the manifest -------------------------------------------------------------

def check_what_this_pr_added(m: dict) -> None:
    """By name; each list of cells held as the prefix it was entered
    with."""
    assert [c["name"] for c in m["configs"]][:6] == \
        ACCEPTED_CONFIGS + [CONFIG]
    assert [w["name"] for w in m["workloads"]][:6] == ACCEPTED_CELLS + [CELL]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC, 1)
    assert "compiles_in_window reads 1" in cell["why"]
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == ["testDurationMinutes"]
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in LISTED:
        cells = by_name[name]["workloads"]
        assert CELL in cells, name
        assert cells[:cells.index(CELL)] == [
            c for c in ACCEPTED_CELLS if c in cells], name
    for name, want in NEW_METRICS.items():
        got = dict(by_name[name])
        assert got.pop("workloads")[:1] == [CELL]
        assert got == {"name": name, **want}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           f"{name}.py"))


def test_what_this_pr_added_to_the_manifest():
    check_what_this_pr_added(manifest())


def test_it_holds_on_a_copy_grown_by_a_later_pr():
    """A later configuration, cell and metric appended to every list
    (as `test_benchmark_cells_pr40.py` grows its copy) leave this cell's
    entries where they were."""
    m = copy.deepcopy(manifest())
    m["configs"].append({"name": "later", "source": "x", "reduced": [],
                         "file": "benchmarks/configs/later.json",
                         "why": "x"})
    m["workloads"].append({"name": "later.feed", "config": "later",
                           "traffic": "feed", "chips": 1, "why": "x"})
    for x in m["end_to_end"] + m["per_layer"]:
        if "workloads" in x:
            x["workloads"].append("later.feed")
    m["per_layer"].append({"name": "later_busy", "unit": "s/s",
                           "better": "lower", "source": "program_counter",
                           "layer": "decode", "moves": "payload_rate",
                           "workloads": ["later.feed"]})
    check_what_this_pr_added(m)
    pr33.check_only_appended_cells(m)


def test_the_cell_resolves_and_reports_what_it_lists():
    plan = run.resolve(manifest(), CELL, dry=False)
    config = plan["config"]
    assert config["name"] == CONFIG and config["chips"] == 1
    assert config["sidecar_flags"] == ["--fanout"]
    assert config["engine"] == "device-batch"
    plain = _json("configs", "plain.json")
    assert config["guarantees"][:5] == plain["guarantees"]
    assert config["guarantees"][5:] == [
        "every subscriber receives the writer's wire, byte for byte, from "
        "its first byte to its last",
        "no subscriber is refused or shed"]
    # the writer's digests are held to the one reference; the readers'
    # bytes to the subscriber kind's `Wire`, named, not copied
    assert config["reference"] == "benchmarks/reference/digests.py"
    replica = config["replica_reference"].split(":")[0]
    assert replica == "benchmarks/sessions/subscriber.py"
    assert os.path.isfile(os.path.join(REPO, replica))
    assert [g["session"] for g in plan["groups"]] == ["digest", "subscriber"]
    reported = {e["name"] for e in plan["end_to_end"]} \
        | {mm["name"] for mm, _ in plan["per_layer"]}
    assert {"payload_rate", "digest_rate", "digest_lag_p95",
            "setup_s"} <= reported
    assert "session_p95" not in reported     # one session a run
    assert set(LISTED) | set(NEW_METRICS) <= reported
    # no hub, no edge loop, no host stream on this leg
    assert not set(pr33.HUB_READERS) & reported
    assert not {"stream_busy", "kernel_hbm_share"} & reported


# -- the traffic --------------------------------------------------------------

FOLLOW = _json("traffic", f"{TRAFFIC}.json")
FEED = _json("traffic", "feed.json")


def test_the_traffic_is_the_feed_writer_and_sixteen_readers():
    writer, readers = FOLLOW["groups"]
    assert writer["name"] == "writer" and writer["session"] == "digest"
    assert writer["generator"] == FEED["generator"] == "closed_loop"
    assert writer["item"] == FEED["item"]               # key for key
    assert (writer["clients"], writer["processes"]) == (1, 1)
    assert writer["session_items"] is None
    assert writer["session_multiple"] == 1024
    assert writer["pool_items"] == FEED["pool_items"] == 16384
    assert "warmup" not in writer and "warmup" not in readers
    assert readers == {**readers, "name": "subscribers",
                       "session": "subscriber", "reads": "writer"}
    assert readers["clients"] == 16 and readers["processes"] >= 8
    assert readers["clients"] // readers["processes"] == 2
    # the slice holds the first program's build (no warm-up: 3-8 s on
    # the chip) and a steady state after it
    assert FOLLOW["trace_slice_s"] == 20
    # the dry run: the feed's dry-run item, 4 readers over 2 processes
    assert writer["dry_run"]["item"] == FEED["dry_run"]["item"]
    assert readers["dry_run"] == {"clients": 4, "processes": 2}
    assert FOLLOW["dry_run"] == {"seconds": 2}


@pytest.mark.parametrize("dry", [False, True])
def test_the_groups_as_the_harness_reads_them(dry):
    traffic = {**FOLLOW, **FOLLOW["dry_run"]} if dry else FOLLOW
    writer, readers = run.groups_of(traffic, dry)
    assert writer["warmup"] == {} and readers["warmup"] == {}
    assert readers["reads"] == "writer" and readers["connect_first"]
    # the readers' generator and parameters are the writer's
    assert readers["params"] is writer["params"]
    assert readers["clients"] == (4 if dry else 16)


# -- the readers --------------------------------------------------------------

DISPATCH = "span.fanout.dispatch.seconds"
LAG = "fanout.frame.latency"
SENT, PUSHES = "fanout.sent.bytes", "fanout.dispatch.writev"


def _ctx(first, last, first_c=None, last_c=None):
    def snap(t, hists, counters):
        return {"monotonic": t, "metrics": {
            "counters": counters or {}, "gauges": {},
            "histograms": {k: {"sum": s, "count": n}
                           for k, (s, n) in hists.items()}}}
    return {"snaps": (snap(100.0, first, first_c),
                      snap(140.0, last, last_c))}


@pytest.mark.parametrize("name, ctx, want", [
    # 12 s of turns over the 40 s window
    ("fanout_dispatch_busy", _ctx({DISPATCH: (3.0, 900)},
                                  {DISPATCH: (15.0, 9000)}), 0.3),
    ("fanout_dispatch_busy", _ctx({}, {DISPATCH: (4.0, 100)}), 0.1),
    # the parent: its turn was an obs-ring span, no histogram
    ("fanout_dispatch_busy", _ctx({}, {}), None),
    # 2,000 marks delivered, 30 s of delay summed: 15 ms a mark
    ("fanout_frame_lag_ms", _ctx({LAG: (10.0, 1000)},
                                 {LAG: (40.0, 3000)}), 15.0),
    ("fanout_frame_lag_ms", _ctx({LAG: (1.0, 10)}, {LAG: (1.0, 10)}), None),
    ("fanout_frame_lag_ms", _ctx({}, {}), None),
    # 64 MiB in 1,024 pushes: 64 KiB a push
    ("fanout_push_kib", _ctx({}, {}, {SENT: 1 << 20, PUSHES: 16},
                             {SENT: 65 << 20, PUSHES: 1040}), 64.0),
    ("fanout_push_kib", _ctx({}, {}, {SENT: 5, PUSHES: 1},
                             {SENT: 5, PUSHES: 1}), None),
    ("fanout_push_kib", _ctx({}, {}, {}, {SENT: 5}), None),
    # no snapshots with them; a dark run: no snapshots
    *[(n, _ctx({}, {}), None) for n in NEW_METRICS],
    *[(n, {"snaps": None}, None) for n in NEW_METRICS],
])
def test_the_new_readers_on_hand_made_snapshots(name, ctx, want):
    got = run.load_reader(name)(ctx)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


# -- the rehearsals -----------------------------------------------------------

def _env() -> dict:
    env = {k: v for k, v in os.environ.items() if not k.startswith("DAT_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return env


def test_dry_run_of_the_new_cell():
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000044", "--trace", "0", "--dry-run"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["dry_run"] is True and last["workload"] == CELL
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True and last["failed"] == 0
    writer, subs = last["groups"]["writer"], last["groups"]["subscribers"]
    assert writer["sessions"] == 1 and writer["bad_sessions"] == 0
    assert writer["attempted"] % 16 == 0 and writer["wire_bytes"] > 0
    assert subs["sessions"] == 4 and subs["bad_sessions"] == 0
    # every subscriber byte compared with the writer's wire
    assert subs["compared_bytes"] == 4 * writer["wire_bytes"]
    assert subs["compared"] == subs["attempted"] == 4 * writer["attempted"]
    assert "connected: group subscribers, 4 clients" in r.stdout


def test_a_lit_rehearsal_reads_the_three_new_readers():
    """The cell at its dry-run sizes with the sidecar lit (as a traced
    run starts it), driven by the harness's own steps: the writer
    connects, then the readers, one t0; the three readers read the
    sidecar's snapshots at the window's ends, and both fan-out spans
    have their histograms."""
    plan = run.resolve(manifest(), CELL, dry=True)
    groups, env = plan["groups"], _env()
    sc = run.Sidecar(list(plan["config"]["sidecar_flags"]), telemetry=True,
                     env=env)
    procs: dict = {}
    clients: list = []
    try:
        for g in groups:
            procs[g["name"]] = [
                run.Client({"session": g["session"],
                            "generator": g["generator"],
                            "traffic": g["params"], "seed": 3000000044,
                            "clients": list(range(i, g["clients"],
                                                  g["processes"])),
                            **({"source_client": 0} if g["reads"] else {})},
                           env)
                for i in range(g["processes"])]
            clients += procs[g["name"]]
        sc.wait_listening(120.0)
        for c in clients:
            c.answer(0, 120.0, "its traffic")
        run.connect_groups(groups, procs, sc.port)
        with tempfile.TemporaryDirectory() as workdir:
            t0, t1, _, tickets = run.open_window(clients, sc.port, 3.0,
                                                 workdir)
            reports = [c.answer(t, 120.0, "the window's report")
                       for c, t in zip(clients, tickets)]
        by_group = run.settle(groups, procs, dict(zip(clients, reports)))
        sc.wait_for(lambda: any(s.get("monotonic", 0) >= t1
                                for s in sc.snapshots), 30.0,
                    "a stats snapshot after the window")
        snaps = (sc.snapshot_near(t0), sc.snapshot_near(t1))
    finally:
        for c in clients:
            c.stop()
        sc.stop()
    subs = by_group["subscribers"]
    assert subs["bad_sessions"] == 0 and subs["failed"] == 0, subs
    assert subs["compared_bytes"] == 4 * by_group["writer"]["wire_bytes"]
    ctx = {"snaps": snaps}
    got = {name: run.load_reader(name)(ctx) for name in NEW_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["fanout_dispatch_busy"] < 1.0
    hists = snaps[1]["metrics"]["histograms"]
    for span in ("fanout.dispatch", "fanout.publish"):
        assert hists[f"span.{span}.seconds"]["count"] > 0, span
