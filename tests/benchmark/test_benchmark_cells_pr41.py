"""The deployment `plainfolder` and its cell `plain.objects` (traffic
`objects`): one publisher shares a folder whose file sizes follow
Impressions' lognormal fit (Agrawal et al., FAST 2009, Table 2), so a
batch spans every slot width and the largest files take the host
stream.  Held here: the manifest's new entries, found by name, with the
accepted cells as a literal prefix of every list (also on a copy grown
as a later PR grows it); the quantile pool the generator draws; the
readers that came with the cell on hand-made snapshots; and a CPU
rehearsal of the cell."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, HERE)
import test_benchmark_cells_pr33 as pr33  # noqa: E402

sys.path.remove(HERE)
sys.path.insert(0, BENCH)
from generators import file_sizes  # noqa: E402

sys.path.remove(BENCH)
run = pr33.run

CELL = "plain.objects"
CONFIG = "plainfolder"
ACCEPTED_CONFIGS = ["plain", "edgehub", "edgehub1g", "meshhub1g"]
# the lists the cell is appended to: every reader it finds something for
LISTED = pr33.STAGE_READERS + ["pump_busy", "pump_wait", "rx_offcpu_share",
                               "launch_offcpu_share", "pack_offcpu_share"]
# the bandwidth share that counts the host stream's blobs as staged
# (`kernel_hbm_share` reads the cell instead)
SILENT = ["blake2b_hbm_share"]
NEW_METRICS = {
    "stream_busy": {"name": "stream_busy", "unit": "s/s", "better": "lower",
                    "source": "program_counter", "layer": "host path",
                    "moves": "payload_rate"},
    "batch_buckets": {"name": "batch_buckets", "unit": "buckets",
                      "better": "lower", "source": "program_counter",
                      "layer": "batching", "moves": "payload_rate"},
    "kernel_pad_share": {"name": "kernel_pad_share", "unit": "%",
                         "better": "lower", "source": "program_counter",
                         "layer": "staging", "moves": "payload_rate"},
    "kernel_hbm_share": {"name": "kernel_hbm_share", "unit": "%",
                         "better": "higher", "source": "device_trace",
                         "layer": "kernels", "moves": "payload_rate"},
}
MIB = 1 << 20


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
    assert [c["name"] for c in m["configs"]][:5] == \
        ACCEPTED_CONFIGS + [CONFIG]
    assert [w["name"] for w in m["workloads"]][:5] == pr33.ACCEPTED + [CELL]
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "objects", 1)
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name in LISTED:
        cells = by_name[name]["workloads"]
        assert CELL in cells, name
        assert cells[:cells.index(CELL)] == [
            c for c in pr33.ACCEPTED if c in cells], name
    for name in SILENT:
        assert CELL not in by_name[name]["workloads"], name
    for name, want in NEW_METRICS.items():
        got = dict(by_name[name])
        assert got.pop("workloads")[:1] == [CELL]
        assert got == want
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           f"{name}.py"))


def test_what_this_pr_added_to_the_manifest():
    check_what_this_pr_added(manifest())


def test_it_holds_on_a_copy_grown_by_a_later_pr():
    """A later configuration, cell and metric appended to every list
    (as `test_benchmark_cells_pr40.py` grows its copy) leave this PR's
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


def test_the_cell_resolves_and_reports_what_it_lists():
    plan = run.resolve(manifest(), CELL, dry=False)
    assert plan["config"]["name"] == CONFIG and plan["config"]["chips"] == 1
    assert plan["config"]["sidecar_flags"] == [] \
        and plan["config"]["engine"] == "device-batch"
    assert plan["traffic"]["generator"] == "file_sizes"
    reported = {e["name"] for e in plan["end_to_end"]} \
        | {mm["name"] for mm, _ in plan["per_layer"]}
    assert {"payload_rate", "digest_rate", "digest_lag_p95",
            "setup_s"} <= reported
    assert "session_p95" not in reported     # one session a run
    assert set(LISTED) | set(NEW_METRICS) <= reported
    assert not set(SILENT) & reported
    # the hub's and the edge loop's readers: this leg has neither
    assert not set(pr33.HUB_READERS) & reported


# -- the pool -----------------------------------------------------------------

OBJECTS = _json("traffic", "objects.json")


def test_the_quantile_pool_is_the_mix_the_cell_was_sized_by():
    sizes = file_sizes.quantile_sizes(OBJECTS["item"]["sizes"],
                                      OBJECTS["pool_items"])
    assert len(sizes) == 2048 and np.all(np.diff(sizes) >= 0)
    assert sizes.max() == 64 * MIB and (sizes == 64 * MIB).sum() == 1
    assert sizes.sum() == pytest.approx(484 * MIB, rel=0.01)
    assert np.median(sizes) == pytest.approx(13_100, rel=0.01)
    assert (sizes <= 4096).mean() == pytest.approx(0.318, abs=0.001)
    streamed = sizes >= 8 * MIB        # the sidecar's default threshold
    assert streamed.sum() == 9
    assert sizes[streamed].sum() / sizes.sum() == pytest.approx(0.386,
                                                                abs=0.001)
    blocks = np.maximum(1, -(-sizes[~streamed] // 128))
    widths = set((1 << np.ceil(np.log2(blocks)).astype(int)).tolist())
    assert widths == {1 << k for k in range(17)}     # 128 B ... 8 MiB


SMALL = {**OBJECTS, **OBJECTS["dry_run"]}


@pytest.mark.parametrize("seed_a, seed_b", [(1, 2), (3000000041, 17)])
def test_every_seed_sends_the_same_sizes_in_its_own_order(seed_a, seed_b):
    a = file_sizes.Pool(SMALL, seed_a, 0)
    b = file_sizes.Pool(SMALL, seed_b, 0)
    want = file_sizes.quantile_sizes(SMALL["item"]["sizes"],
                                     SMALL["pool_items"])
    assert sorted(a.payload_len) == sorted(b.payload_len) == want.tolist()
    assert a.payload_len != b.payload_len
    assert a.digests != b.digests
    again = file_sizes.Pool(SMALL, seed_a, 0)
    assert again.frames == a.frames and again.digests == a.digests


@pytest.mark.parametrize("seed", [1, 3000000041])
def test_the_order_is_one_shuffle_of_the_whole_pool(seed):
    """The seed permutes the whole pool once, as `Pool` draws it: how
    the sizes fall between the session's batches is the seed's."""
    want = file_sizes.quantile_sizes(SMALL["item"]["sizes"],
                                     SMALL["pool_items"])
    rng = np.random.default_rng([seed, 41, 0])
    assert file_sizes.Pool(SMALL, seed, 0).payload_len == \
        rng.permutation(want).tolist()


def test_the_cap_clips_the_tail():
    capped = dict(SMALL["item"]["sizes"], max_bytes=64 << 10)
    sizes = file_sizes.quantile_sizes(capped, 64)
    free = file_sizes.quantile_sizes(SMALL["item"]["sizes"], 64)
    assert sizes.max() == 64 << 10 < free.max()
    assert np.array_equal(sizes, np.minimum(free, 64 << 10))


def test_frames_and_digests_are_the_references():
    pool = file_sizes.Pool(SMALL, 5, 0)
    ref = file_sizes.ref
    for frame, n, digest in zip(pool.frames, pool.payload_len,
                                pool.digests):
        head = ref.frame_header(n, ref.FRAME_TYPE["blob"])
        assert frame[:len(head)] == head and len(frame) == len(head) + n
        assert digest == ref.digest(frame[len(head):])


def test_the_generator_streams_one_session_to_whole_batches():
    traffic = file_sizes.ClientTraffic(SMALL, 9, 0)
    plan = traffic.next_session(stop_at=0.0)
    # the window is over at once: the session still ends on a whole batch
    while (piece := plan.chunk(1.0)) is not None:
        plan.advance(len(piece))
    assert len(plan.frame_ends) == SMALL["session_multiple"]
    lone = traffic.lone_session(SMALL["pool_items"])
    assert lone.plan.digests == traffic.pool.digests
    with pytest.raises(ValueError, match="session_items"):
        file_sizes.ClientTraffic({**SMALL, "session_items": 16}, 9, 0)


# -- the readers --------------------------------------------------------------

def _snap(t, hists, rows=None):
    return {"monotonic": t, "blake2b_buckets": rows or {}, "metrics": {
        "counters": {}, "gauges": {},
        "histograms": {k: {"sum": s, "count": n}
                       for k, (s, n) in hists.items()}}}


def _ctx(first, last, rows=({}, {}), **more):
    return {"snaps": (_snap(100.0, first, rows[0]),
                      _snap(140.0, last, rows[1])), **more}


def _row(dispatches, items, padded_items, payload_bytes=None):
    """A `blake2b_buckets` row; no `payload_bytes`: a parent's."""
    row = {"dispatches": dispatches, "items": items,
           "padded_items": padded_items}
    if payload_bytes is not None:
        row["payload_bytes"] = payload_bytes
    return row


STREAM = "span.digest.stream.seconds"
DISPATCH = "span.digest.dispatch.seconds"
# two buckets a dispatch before the window; in it 100 dispatches of
# three, a 64-block bucket new among them
BUCKET_ROWS = ({"pallas:1": _row(2, 20, 2048), "pallas:8": _row(2, 2, 2048)},
               {"pallas:1": _row(102, 20, 2048),
                "pallas:8": _row(102, 2, 2048),
                "pallas:64": _row(100, 5, 51200)})
# 4 dispatches of a 2-block (256 B) slot at 1,024 rows, the kernel handed
# a quarter of the 1 MiB staged; the parent notes no payload bytes
STAGED = ({}, {"pallas:2": _row(4, 4096, 4096, 1 << 18)})
PARENT = ({}, {"pallas:2": _row(4, 4096, 4096)})
# 10 bucket dispatches of 819 MB each; 5 runs, one device second
TRACE = {"programs": {"jit_blake2b_words_pallas": [1.0, 5],
                      "jit_dynamic_slice": [3.0, 7]}}
PEAKS = {"hbm_bytes_per_s": 819e9}
READ = ({}, {"pallas:16": _row(10, 10, 10240, 8_190_000_000)})


@pytest.mark.parametrize("name, ctx, want", [
    # 8 s of hashing over the 40 s window
    ("stream_busy", _ctx({STREAM: (1.0, 50)}, {STREAM: (9.0, 450)}), 0.2),
    ("stream_busy", _ctx({}, {STREAM: (4.0, 200)}), 0.1),
    # registered, no stream in the window: nothing, not 0
    ("stream_busy", _ctx({STREAM: (1.0, 50)}, {STREAM: (1.0, 50)}), None),
    ("batch_buckets", _ctx({DISPATCH: (0.1, 2)}, {DISPATCH: (3.0, 102)},
                           BUCKET_ROWS), 3.0),
    ("batch_buckets", _ctx({DISPATCH: (0.1, 2)}, {DISPATCH: (0.1, 2)},
                           BUCKET_ROWS), None),
    ("kernel_pad_share", _ctx({}, {}, STAGED), 75.0),
    ("kernel_pad_share", _ctx({}, {}, PARENT), None),
    ("kernel_hbm_share", _ctx({}, {}, READ, trace=TRACE, peaks=PEAKS), 0.5),
    ("kernel_hbm_share", _ctx({}, {}, READ, trace=None, peaks=PEAKS), None),
    ("kernel_hbm_share", _ctx({}, {}, PARENT, trace=TRACE, peaks=PEAKS),
     None),
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


# -- the rehearsal ------------------------------------------------------------

def test_dry_run_of_the_new_cell():
    env = {k: v for k, v in os.environ.items() if not k.startswith("DAT_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000041", "--trace", "0", "--dry-run"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["dry_run"] is True and last["workload"] == CELL
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True and last["failed"] == 0
    # the lone warm-up session of the whole pool, then the endless one
    assert last["sessions"] == 2
    assert last["compared"] == last["attempted"] >= 2 * SMALL["pool_items"]
    assert "0 sessions broke a guarantee" in r.stdout
