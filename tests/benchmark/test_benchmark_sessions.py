"""Session kinds and client groups in the harness: a kind is a file
`benchmarks/sessions/<kind>.py` found by name (a traffic file without
`"session"` is the digest kind, as every accepted file is); a traffic
file with `"groups"` connects its groups in order and gives them one t0;
the processes' timings are merged over every key any of them wrote.

The second kind, the fan-out subscriber, on hand-made streams (its
verdict and `settle` against the writer's wire) and marks (the two
figures `run.py` prints), and in a CPU rehearsal: a copy of the
benchmark given a `--fanout` configuration, a traffic file of a writer
group and a subscriber group, and a cell appended to a copy of the
manifest.  `fanout_copy` builds that copy; run without `--dry-run`, at
`publish`'s full sizes, it is the rehearsal's run on the chip."""

import copy
import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)
import client  # noqa: E402
import metrics  # noqa: E402
import run  # noqa: E402
from generators import closed_loop  # noqa: E402
from reference import digests as ref  # noqa: E402
from sessions import digest, subscriber  # noqa: E402

sys.path.remove(BENCH)

ACCEPTED_TRAFFIC = ["publish", "feed", "publish8", "publish8m", "objects"]


def _traffic(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


# -- kinds are files ----------------------------------------------------------

@pytest.mark.parametrize("kind", ["digest", "subscriber"])
def test_a_session_kind_is_found_by_name(kind):
    for mod in (client.load_session(kind), run.load_session(kind)):
        for hook in ("Clients", "Conn", "counts", "arrays"):
            assert hasattr(mod, hook), (kind, hook)
    assert hasattr(run.load_session(kind), "settle") is (kind == "subscriber")


def test_a_kind_that_is_no_file_is_named():
    with pytest.raises(FileNotFoundError, match="sessions/absent.py"):
        client.load_session("absent")
    with pytest.raises(FileNotFoundError, match="session kind"):
        client.load_session("_refusal")      # a helper, not a kind
    with pytest.raises(run.BenchFailure, match="sessions/absent.py"):
        run.load_session("absent")


@pytest.mark.parametrize("name", ACCEPTED_TRAFFIC)
def test_a_traffic_file_without_session_is_one_digest_group(name):
    traffic = _traffic(name)
    assert "session" not in traffic and "groups" not in traffic
    for dry in (False, True):
        t = {**traffic, **traffic["dry_run"]} if dry else traffic
        (g,) = run.groups_of(t, dry)
        assert g["session"] == "digest" and not g["connect_first"]
        assert g["params"] is t           # the generator reads the file
        assert (g["clients"], g["processes"], g["warmup"]) == (
            t["clients"], t["processes"], t.get("warmup", {}))


# -- groups: connect order, one t0 ---------------------------------------------

class _Proc:
    """A client process as run.py drives it, answering at once."""

    def __init__(self, group: str, log: list):
        self.group, self.log, self.lines = group, log, []

    def send(self, cmd: dict) -> int:
        self.log.append((time.monotonic(), self.group, cmd))
        self.lines.append({"connected": 1} if cmd["cmd"] == "connect"
                          else {})
        return len(self.lines) - 1

    def answer(self, ticket: int, timeout: float, what: str) -> dict:
        return self.lines[ticket]


def _grouped(writers=1, readers=2):
    pub = _traffic("publish")
    return run.groups_of({"groups": [
        {"name": "writer", "generator": "closed_loop", **_writer(pub),
         "clients": writers, "processes": 1},
        {"name": "subscribers", "session": "subscriber", "reads": "writer",
         "clients": readers, "processes": readers}]}, dry=False)


def test_groups_connect_in_order_then_share_one_t0(tmp_path):
    log: list = []
    groups = _grouped()
    procs = {g["name"]: [_Proc(g["name"], log)
                         for _ in range(g["processes"])] for g in groups}
    run.connect_groups(groups, procs, 4242)
    clients = procs["writer"] + procs["subscribers"]
    t0, t1, outs, _ = run.open_window(clients, 4242, 3.0, str(tmp_path))
    connects = [(t, g) for t, g, c in log if c["cmd"] == "connect"]
    loops = [c for _, _, c in log if c["cmd"] == "loop"]
    assert [g for _, g in connects] == ["writer", "subscribers",
                                        "subscribers"]
    # the subscribers connect after the writer's connection and a settle
    assert connects[1][0] - connects[0][0] >= run.CONNECT_SETTLE_S
    assert len(loops) == 3 and {c["t0"] for c in loops} == {t0}
    assert t0 > connects[-1][0] and t1 == t0 + 3.0
    assert len(set(outs)) == 3


def test_a_group_reads_a_group_of_one_client_before_it():
    with pytest.raises(run.BenchFailure, match="one broadcast"):
        _grouped(writers=2)
    with pytest.raises(run.BenchFailure, match="no group before it"):
        run.groups_of({"groups": [
            {"name": "subscribers", "session": "subscriber",
             "reads": "writer", "clients": 1, "processes": 1}]}, dry=False)


class _Listener:
    """A server that accepts and records when each connection came and
    when its first byte did."""

    def __init__(self):
        self.sock = socket.create_server(("127.0.0.1", 0))
        self.port = self.sock.getsockname()[1]
        self.accepted: list = []
        self.first_byte: list = []
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        conn, _ = self.sock.accept()
        self.accepted.append(time.monotonic())
        with conn:
            conn.recv(1)
            self.first_byte.append(time.monotonic())
            conn.shutdown(socket.SHUT_RDWR)


@pytest.mark.parametrize("grouped", [True, False])
def test_a_client_sends_nothing_before_t0(grouped):
    """A grouped client connects on the `connect` command and holds its
    first byte until t0; an ungrouped one connects at t0, as before."""
    srv = _Listener()
    plan = closed_loop.ClientTraffic(
        {"item": {"kind": "blob", "bytes": 64}, "session_items": 2,
         "pool_items": 2}, 1, 0).next_session(0.0)
    opened = {0: client.connect(srv.port)} if grouped else {}
    t0 = time.monotonic() + 0.4
    conns = client.run_sessions(
        digest.Conn, lambda slot, p=[plan]: p.pop() if p else None, 1,
        srv.port, t0, t0 + 0.1, opened)
    srv.thread.join(10)
    assert not srv.thread.is_alive() and len(conns) == 1
    assert srv.first_byte[0] >= t0
    assert (srv.accepted[0] < t0) is grouped


# -- the merge of timings ------------------------------------------------------

def test_timings_merge_over_the_union_of_keys(tmp_path):
    a, b, c = (str(tmp_path / f"t{i}.npz") for i in range(3))
    np.savez(a, item_t=np.array([1.0, 2.0]), item_lag=np.array([0.1, 0.2]))
    np.savez(b, sub_recv_t=np.array([3.0]), sub_recv_bytes=np.array([7]))
    np.savez(c, item_t=np.array([4.0]), item_lag=np.array([0.4]))
    got = run.merge_timings([a, b, c])
    assert set(got) == {"item_t", "item_lag", "sub_recv_t",
                        "sub_recv_bytes"}
    assert got["item_t"].tolist() == [1.0, 2.0, 4.0]
    assert got["sub_recv_bytes"].tolist() == [7]


# -- the subscriber on hand-made streams ---------------------------------------

WRITER = {"item": {"kind": "blob", "bytes": 1000}, "session_items": None,
          "session_multiple": 4, "pool_items": 3}
FRAMES = 8                       # the writer's session: 8 frames


def _writer_wire() -> bytes:
    """The writer's session of FRAMES frames, as its client sends it."""
    plan = closed_loop.ClientTraffic(WRITER, 11, 0).next_session(0.0)
    out = bytearray()
    while (piece := plan.chunk(-1.0)) is not None:
        out += piece
        plan.advance(len(piece))
        if len(plan.frame_ends) == FRAMES and plan._view is None:
            break
    return bytes(out)


def _subscriber():
    wire = subscriber.Clients(
        closed_loop, {"traffic": WRITER, "seed": 11, "source_client": 0,
                      "clients": [0]}).next_plan(0, 0.0)
    return subscriber.Conn(wire, _Sock())


class _Sock:
    def close(self):
        pass


def _refusal(kind: str) -> bytes:
    return (json.dumps({"fanout_peer": "p2:127.0.0.1:5", "ok": False,
                        kind: True}) + "\n").encode()


@pytest.mark.parametrize("case, fault", [
    ("equal", None),
    ("one_byte_flipped", "differs from the writer's wire"),
    ("short", "shed or cut short"),
    ("snapshot_needed", "refused (snapshot_needed)"),
    ("rejected", "refused (rejected)"),
    ("not_source", "refused (not_source)"),
    ("no_bytes", "no byte received"),
])
def test_the_subscriber_verdict(case, fault):
    wire = _writer_wire()
    flipped = bytearray(wire)
    flipped[1234] ^= 0x40
    streams = {"equal": wire, "one_byte_flipped": bytes(flipped),
               "short": wire[:-1500], "no_bytes": b""}
    stream = streams[case] if case in streams else _refusal(case)
    c = _subscriber()
    # odd receive sizes, across the generator's frames
    for at in range(0, len(stream), 777):
        c.take(np.frombuffer(stream[at:at + 777], np.uint8), 100.0 + at)
    c.close()
    got = subscriber.judge(c.verdict(), len(wire), FRAMES)
    assert got["attempted"] == FRAMES
    if fault is None:
        assert got == {"attempted": FRAMES, "failed": 0,
                       "compared": FRAMES, "fault": None}
        assert c.matched == c.received == len(wire)
    else:
        assert fault in got["fault"] and got["failed"] == FRAMES
    if case == "one_byte_flipped":
        assert c.first_bad == 1234 and c.matched == 1234


def test_settle_holds_every_subscriber_to_the_writer():
    """Two subscribers, one of them shed half way: the group's counts
    once the writer's are in."""
    wire = _writer_wire()
    conns = [_subscriber(), _subscriber()]
    conns[0].take(np.frombuffer(wire, np.uint8), 1.0)
    conns[1].take(np.frombuffer(wire[:len(wire) // 2], np.uint8), 1.0)
    verdicts = [c.verdict() for c in conns]
    rep = {"sessions": 2, "attempted": 0, "failed": 0, "compared": 0,
           "bad_sessions": 0, "first_fault": None,
           **subscriber.counts(conns, verdicts)}
    got = subscriber.settle(rep, {"wire_bytes": len(wire),
                                  "attempted": FRAMES})
    assert (got["attempted"], got["failed"], got["bad_sessions"]) == (
        2 * FRAMES, FRAMES, 1)
    assert 0 < conns[1].frames < FRAMES
    assert got["compared"] == FRAMES + conns[1].frames
    assert "shed or cut short" in got["first_fault"]
    assert got["compared_bytes"] == len(wire) + len(wire) // 2


@pytest.mark.parametrize("sending", [False, True])
def test_a_refused_writer_says_so(sending):
    """A digest session answered by a refusal record (the writer that
    found the source slot taken) fails whole, and names the record,
    whether or not it had sent everything."""
    plan = closed_loop.ClientTraffic(
        {"item": {"kind": "change", "value_bytes": 10,
                  "key_distribution": "uniform", "key_space": 10},
         "session_items": 2, "pool_items": 2}, 3, 0).next_session(0.0)
    c = digest.Conn(plan, _Sock())
    c.write_open = sending
    c._compare(_refusal("not_source"))
    c.close()
    v = c.verdict()
    assert v["failed"] == 2 and "refused (not_source)" in v["fault"]


# -- the two printed figures ---------------------------------------------------

def test_the_subscriber_figures_on_hand_made_marks():
    # the writer sent frame k's last byte at 10 + k; two subscribers got
    # it 5 ms and 25 ms later; frame 9 came after the window's end
    k = np.arange(10)
    sent = 10.0 + k
    timings = {
        "item_t": sent + 0.5, "item_lag": np.full(10, 0.5),
        "sub_frame_k": np.concatenate([k, k]),
        "sub_frame_t": np.concatenate([sent + 0.005, sent + 0.025]),
        # 1 MiB a receive, every 0.5 s, from 10.0 to 19.0
        "sub_recv_t": 10.0 + 0.5 * np.arange(19),
        "sub_recv_bytes": np.full(19, 1 << 20),
    }
    got = metrics.fanout(timings, t0=9.5, t1=18.5)
    # edge to edge: 10.0 … 18.5, the first receive the edge
    assert got["subscriber_mib_per_s"] == pytest.approx(17 / 8.5)
    assert got["subscriber_delay_samples"] == 18
    assert got["subscriber_delay_p50_ms"] == pytest.approx(5.0)
    assert got["subscriber_delay_p95_ms"] == pytest.approx(25.0)
    assert metrics.fanout({"item_t": sent}, 9.5, 18.5) is None


# -- the rehearsal -------------------------------------------------------------

CELL = "fanout.subscribe"


def _writer(mix: dict) -> dict:
    """A mix's item and pool as one writer: one session a window that
    ends on whole 1,024-item batches, no warm-up."""
    keep = ("item", "pool_items")
    return {**{k: mix[k] for k in keep}, "session_items": None,
            "session_multiple": 1024, "dry_run": {
                **{k: v for k, v in mix["dry_run"].items() if k in keep},
                "session_multiple": 16}}


def fanout_copy(root, subscribers: int = 2, processes: int = 1,
                sidecar_flags=("--fanout",), writer: str = "publish") -> str:
    """A copy of the benchmark under `root` with a `--fanout`
    configuration, a traffic file of one writer (the item of the mix
    `writer`) and its subscribers, and the cell `fanout.subscribe`
    appended to a copy of the manifest; its path."""
    root = str(root)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    with open(os.path.join(BENCH, "configs", "plain.json")) as f:
        cfg = json.load(f)
    cfg.update(
        name="fanout", sidecar_flags=list(sidecar_flags),
        source="this repository: sidecar.py `--tcp --fanout` (ROADMAP "
               "aim 2: one writer, many readers), other flags at their "
               "defaults",
        guarantees=cfg["guarantees"] + [
            "every subscriber receives the writer's wire, byte for "
            "byte, from its first byte to its last"])
    cfg.pop("assumed", None)
    traffic = {
        "name": "fanout", "source": "BASELINE.json configs[2] (1 MiB "
        "blobs), one writer and its subscribers",
        "groups": [
            {"name": "writer", "session": "digest",
             "generator": "closed_loop", **_writer(_traffic(writer)),
             "clients": 1, "processes": 1},
            {"name": "subscribers", "session": "subscriber",
             "reads": "writer", "clients": subscribers,
             "processes": processes}],
        "trace_slice_s": 6, "dry_run": {"seconds": 2}}
    for name, body in (("configs/fanout.json", cfg),
                       ("traffic/fanout.json", traffic)):
        with open(os.path.join(root, "benchmarks", name), "w") as f:
            json.dump(body, f, indent=1)
    m = copy.deepcopy(m)
    m["configs"].append({"name": "fanout", "source": cfg["source"],
                         "file": "benchmarks/configs/fanout.json",
                         "reduced": [], "why": "a rehearsal"})
    m["workloads"].append({"name": CELL, "config": "fanout",
                           "traffic": "fanout", "chips": 1,
                           "why": "a rehearsal"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f, indent=1)
    return root


def test_the_fanout_rehearsal_on_the_cpu(tmp_path):
    root = fanout_copy(tmp_path)
    env = {k: v for k, v in os.environ.items() if not k.startswith("DAT_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000043", "--trace", "0",
         "--dry-run"],
        cwd=root, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0, last
    writer, subs = last["groups"]["writer"], last["groups"]["subscribers"]
    assert writer["sessions"] == 1 and writer["wire_bytes"] > 0
    assert subs["sessions"] == 2 and subs["bad_sessions"] == 0
    assert subs["compared_bytes"] == 2 * writer["wire_bytes"]
    assert subs["attempted"] == subs["compared"] == 2 * writer["attempted"]
    window = next(json.loads(ln.split("window: ", 1)[1]) for ln in lines
                  if ln.startswith("bench: window: "))
    assert window["subscriber_mib_per_s"] > 0
    assert 0 < window["subscriber_delay_p50_ms"] \
        <= window["subscriber_delay_p95_ms"]
    assert "connected: group writer, 1 clients" in r.stdout
    assert "connected: group subscribers, 2 clients" in r.stdout
