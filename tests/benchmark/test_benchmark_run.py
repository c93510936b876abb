"""run.py end to end on the CPU: one `--dry-run` of each configuration,
and the refusals — no TPU, no package beside the benchmark, a reply that
is wrong."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
RUN = os.path.join(BENCH, "run.py")


def _run(args, cwd=REPO, script=RUN, timeout=240):
    # other tests of a worker leave DAT_* overrides in os.environ
    env = {k: v for k, v in os.environ.items() if not k.startswith("DAT_")}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("cell, sessions_at_least", [
    ("plain.publish", 2),      # the warm-up session and the endless one
    ("edgehub.feed", 10),
])
def test_dry_run_of_each_configuration(cell, sessions_at_least):
    r = _run(["--workload", cell, "--seed", "3000000019", "--trace", "0",
              "--dry-run"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["dry_run"] is True and last["workload"] == cell
    # nothing a reader could take for a chip result
    assert "metrics" not in last and "device" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"] == last["attempted"] > 0
    assert last["sessions"] >= sessions_at_least
    text = "\n".join(lines[:-1])
    for said in ("host cpus", "sidecar: device engine=", "compared",
                 "benchmarks/reference/digests.py", "window:",
                 "cpu in the window", "client processes"):
        assert said in text, said
    assert all(ln.startswith("bench: ") for ln in lines[:-1])


def test_without_a_tpu_nothing_is_served_and_nothing_is_reported():
    r = _run(["--workload", "plain.publish", "--seed", "1", "--seconds",
              "1", "--trace", "0"])
    assert r.returncode != 0
    assert "no accelerator" in r.stdout
    assert 'platform="cpu"' in r.stdout       # the sidecar's own line
    assert "-- sidecar stderr, last lines --" in r.stdout
    assert not r.stdout.strip().splitlines()[-1].startswith("{")


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(["--workload", "plain.publish", "--seed", "1", "--seconds",
              "1", "--trace", "0"], cwd=tmp_path,
             script=str(tmp_path / "benchmarks" / "run.py"))
    assert r.returncode != 0
    assert "the sidecar exited" in r.stdout
    assert "dat_replication_protocol_tpu" in r.stdout
    assert not r.stdout.strip().splitlines()[-1].startswith("{")


def test_the_parent_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import run, procs, "
            "metrics, client, reduce_trace; "
            "assert 'jax' not in sys.modules and "
            "'dat_replication_protocol_tpu' not in sys.modules" % BENCH)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stderr


# -- the comparison: a wrong, missing, extra or torn reply is a failure --------
# (the digest session kind, `sessions/digest.py`)

sys.path.insert(0, BENCH)
from generators import closed_loop  # noqa: E402
from reference import digests as ref  # noqa: E402
from sessions import digest  # noqa: E402

sys.path.remove(BENCH)

TRAFFIC = {"item": {"kind": "change", "value_bytes": 100,
                    "key_distribution": "uniform", "key_space": 1000},
           "session_items": 4, "pool_items": 8}


class _Sock:
    def close(self):
        pass


def _conn(plan):
    c = digest.Conn(plan, _Sock())
    c.write_open = False      # the session has sent everything
    return c


def _reencoded(kind, seq, dig):
    """The right answer, fields in another order than the canonical."""
    k = f"{kind}-{seq}".encode()
    s = f"digest:{kind}".encode()
    p = (b"\x32" + ref.uvarint(len(dig)) + dig + b"\x28\x01\x20\x00"
         + b"\x18" + ref.uvarint(seq) + b"\x12" + ref.uvarint(len(k)) + k
         + b"\x0a" + ref.uvarint(len(s)) + s)
    return ref.frame_header(len(p), ref.TYPE_CHANGE) + p


@pytest.mark.parametrize("case, failed, fault", [
    ("right", 0, None),
    ("right_split_mid_frame", 0, None),
    ("right_other_encoding", 0, None),
    ("wrong_digest", 1, "digest differs from hashlib"),
    ("out_of_order", 2, "key is"),
    ("one_missing", 1, "3 digests for 4 items"),
    ("none_at_all", 4, "rejected or shed"),
    ("one_extra", 4, "never sent"),
    ("torn_end", 4, "inside a frame"),
])
def test_every_reply_is_held_to_the_reference(case, failed, fault):
    plan = closed_loop.ClientTraffic(TRAFFIC, 5, 0).next_session(0.0)
    digs = [plan.want(i)[2] for i in range(4)]
    frames = [ref.expected_reply("change", i, d) for i, d in enumerate(digs)]
    assert b"".join(frames) == plan.exp
    assert all(d == ref.digest(plan.plan.wire[e - n:e]) for d, e, n in zip(
        digs, plan.frame_ends, [b - a for a, b in zip(
            plan.pay_cum, plan.pay_cum[1:])]))
    chunks = {
        "right": [b"".join(frames)],
        "right_split_mid_frame": [frames[0] + frames[1][:9],
                                  frames[1][9:] + b"".join(frames[2:])],
        "right_other_encoding": [frames[0], _reencoded("change", 1, digs[1]),
                                 frames[2] + frames[3]],
        "wrong_digest": [frames[0] + ref.expected_reply(
            "change", 1, bytes(32)) + frames[2] + frames[3]],
        "out_of_order": [frames[1] + frames[0] + frames[2] + frames[3]],
        "one_missing": [b"".join(frames[:3])],
        "none_at_all": [],
        "one_extra": [b"".join(frames) + frames[3]],
        "torn_end": [b"".join(frames) + frames[0][:5]],
    }[case]
    c = _conn(plan)
    for ch in chunks:
        c._compare(ch)
    c.close()
    v = c.verdict()
    assert v["attempted"] == 4 and v["failed"] == failed, v
    if fault is None:
        assert v["fault"] is None and v["compared"] == 4
    else:
        assert fault in v["fault"]
