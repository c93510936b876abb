"""reduce_trace.py on small fixtures: hand-made intervals whose answer
can be worked out on paper, and a trimmed recording from the chip."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmarks")
sys.path.insert(0, BENCH)
import reduce_trace as rt  # noqa: E402

sys.path.remove(BENCH)

MS = 1_000_000     # ns

HLO = ("%blake2b_native.1 = (u32[8,8,128]{2,1,0:T(8,128)S(1)}, "
       "u32[8,8,128]{2,1,0:T(8,128)S(1)}) custom-call(u32[8,128]{1,0:T(8,128)"
       "S(1)} %bitcast.19, u32[8192,16,8,128]{3,2,1,0:T(8,128)} %copy.2), "
       "custom_call_target=\"tpu_custom_call\"")
COPY = ("%copy.2 = u32[8192,16,8,128]{3,2,1,0:T(8,128)} "
        "copy(u32[8192,16,8,128]{0,1,3,2:T(8,128)} %bitcast.2)")


def hand_made() -> dict:
    """A 1,000 ms slice.  Two program runs: ops at [100,110)+[110,140) and
    [600,610)+[610,640) ms, so 80 ms busy and gaps [0,100) [140,600)
    [640,1000).  Host spans: digest.dispatch [50,400), with
    digest.collect [90,95) nested in it; a second thread's
    sidecar.session.recv [300,700); nothing after 700."""
    ops = [(COPY, 100 * MS, 10 * MS), (HLO, 110 * MS, 30 * MS),
           (COPY, 600 * MS, 10 * MS), (HLO, 610 * MS, 30 * MS),
           (COPY, 2000 * MS, 10 * MS)]                # outside the slice
    mods = [("jit_blake2b_packed_pallas(123)", 100 * MS, 40 * MS),
            ("jit_blake2b_packed_pallas(123)", 600 * MS, 40 * MS),
            ("jit_blake2b_packed_pallas(123)", 2000 * MS, 10 * MS)]
    spans = [("digest.dispatch", 50 * MS, 350 * MS),
             ("digest.collect", 90 * MS, 5 * MS),
             ("sidecar.session.recv", 300 * MS, 400 * MS)]
    return {"devices": [{"plane": "/device:TPU:0",
                         "XLA Modules": rt._columns(mods),
                         "XLA Ops": rt._columns(ops)}],
            "host_spans": rt._columns(spans),
            "marks": {"begin_ns": 0, "end_ns": 1000 * MS}, "seen": {}}


def test_hand_made_intervals():
    out = rt.reduce(hand_made())
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(1.0)
    assert out["busy_s"] == pytest.approx(0.080)
    assert out["programs"] == {
        "jit_blake2b_packed_pallas": [pytest.approx(0.080), 2]}
    assert out["device_ops"] == [
        ["%blake2b_native.1 custom-call", pytest.approx(0.060)],
        ["%copy.2 copy", pytest.approx(0.020)]]
    gaps = dict(out["idle_gaps"])
    # [0,50) no span; [50,90) dispatch; [90,95) collect; [95,100)
    # dispatch; [140,300) dispatch; [300,400) recv (started last);
    # [400,600) recv; [640,700) recv; [700,1000) no span
    assert gaps["no span"] == pytest.approx(0.350)
    assert gaps["digest.dispatch"] == pytest.approx(0.205)
    assert gaps["digest.collect"] == pytest.approx(0.005)
    assert gaps["sidecar.session.recv"] == pytest.approx(0.360)
    assert sum(gaps.values()) == pytest.approx(1.0 - 0.080)
    assert out["longest_gap_s"] == pytest.approx(0.460)
    assert out["idle_gaps"][0][0] == "sidecar.session.recv"   # by time


@pytest.mark.parametrize("edit, want", [
    # a second chip that never ran: busy is the mean over the chips
    (lambda d: d["devices"].append({"plane": "/device:TPU:1"}), 0.040),
    # the slice's marks cut an operation in two
    (lambda d: d["marks"].update(begin_ns=120 * MS), 0.020 + 0.040),
    # overlapping operations (two cores' lines merged) count once
    (lambda d: d["devices"][0].update({"XLA Ops": rt._columns(
        [("a", 100 * MS, 50 * MS), ("b", 120 * MS, 50 * MS)])}), 0.070),
])
def test_busy_time(edit, want):
    d = hand_made()
    edit(d)
    assert rt.reduce(d)["busy_s"] == pytest.approx(want)


def test_a_trace_without_a_device_plane_is_an_error():
    d = hand_made()
    d["devices"] = []
    d["seen"] = {"/host:CPU": {}}
    with pytest.raises(ValueError, match="/host:CPU"):
        rt.reduce(d)


@pytest.mark.parametrize("name, short", [
    (HLO, "%blake2b_native.1 custom-call"),
    (COPY, "%copy.2 copy"),
    ("%copy-done = u32[1024]{0:T(1024)S(1)} copy-done((u32[1024]{0:T(1024)"
     "S(1)}, u32[]{:S(2)}) %copy-start)", "%copy-done copy-done"),
    ("%bitcast_bitcast_fusion = u32[1024,8]{0,1:T(8,128)} fusion(u32[1,8]"
     "{3,2,1,0} %bitcast.18), kind=kLoop", "%bitcast_bitcast_fusion fusion"),
    ("plain name", "plain name"),
    ("x" * 500, "x" * 120),
])
def test_operation_names_are_cut_to_a_row(name, short):
    assert rt.short_op(name) == short


def test_recording_from_the_chip():
    """The slice `plain.publish` wrote on the v5e in PR 24 (trimmed by
    reduce_trace.py --dump: it keeps only what reduce() reads)."""
    with open(os.path.join(HERE, "fixtures", "trace_plain_publish.json")) as f:
        dump = json.load(f)
    out = rt.reduce(dump)
    assert out["devices"] == 1
    assert 5.5 < out["window_s"] < 7.0
    assert list(out["programs"]) == ["jit_blake2b_packed_pallas"]
    secs, runs = out["programs"]["jit_blake2b_packed_pallas"]
    assert runs >= 1 and 0.012 < secs / runs < 0.016      # 13.8 ms a GiB
    assert out["busy_s"] == pytest.approx(secs, rel=1e-3)
    assert out["device_ops"][0][0].endswith("custom-call")
    assert 1.0 - out["busy_s"] / out["window_s"] > 0.99
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
    assert "digest.dispatch" in dict(out["idle_gaps"])
