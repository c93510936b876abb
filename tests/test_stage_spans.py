"""Stage spans on the served digest path (ISSUE 26).

One ``utils.trace.span`` call at each layer boundary, read three ways: a
profiler annotation always, and lit an obs-ring record (parent link,
fields, the batch ordinal) plus one ``span.<name>.seconds`` observation.
Under test here:

* the stages of one dispatch + flush: each recorded once, children
  linked to ``digest.dispatch`` / ``digest.collect``, one ``batch``, and
  children's seconds inside the parent's;
* one observation per batch of every stage histogram and of the two
  queue clocks, through a private pipeline and through a two-session hub;
* the lit counters moved per item before this PR and per run after it:
  their totals at quiescence are what they were;
* ``OBS.frames``: ``--stats-fd`` alone records no per-frame instant,
  ``--trace-jsonl`` still does;
* the dark path of every new site: a ``span()`` call and nothing else;
* ISSUE 37: ``digest.launch`` split into child spans where the work
  happens, and every stage span's second clock — the thread's CPU
  seconds as field ``cpu`` and ``span.<name>.cpu_seconds``, on one
  visit in ``CPU_CLOCK_EVERY`` of a name — on spans that sleep and
  spans that burn; ``recv_fetch``'s pair of clocks.
"""

import dis
import hashlib
import inspect
import itertools
import os
import socket
import subprocess
import sys
import time
import types

import pytest

from dat_replication_protocol_tpu import sidecar
from dat_replication_protocol_tpu.backend import tpu_backend
from dat_replication_protocol_tpu.backend.tpu_backend import DigestPipeline
from dat_replication_protocol_tpu.edge.loop import EdgeLoop
from dat_replication_protocol_tpu.hub import ReplicationHub
from dat_replication_protocol_tpu.hub import engine as hub_engine
from dat_replication_protocol_tpu.obs import metrics as obs_metrics
from dat_replication_protocol_tpu.obs.tracing import SPANS
from dat_replication_protocol_tpu.ops import blake2b as blake2b_mod
from dat_replication_protocol_tpu.session import pump as pump_mod
from dat_replication_protocol_tpu.utils import trace as trace_mod

from test_wire_fixtures import SESSION_4

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DISPATCH_STAGES = ("digest.pack", "digest.h2d", "digest.launch")
COLLECT_STAGES = ("digest.d2h_wait", "digest.unpack")
DIGEST_STAGES = ("digest.dispatch", *DISPATCH_STAGES, "digest.collect",
                 *COLLECT_STAGES, "digest.deliver")


def _hist(name: str) -> dict:
    return obs_metrics.snapshot()["histograms"].get(
        name, {"count": 0, "sum": 0.0})


def _counters() -> dict:
    return obs_metrics.snapshot()["counters"]


def _blake(p: bytes) -> bytes:
    return hashlib.blake2b(p, digest_size=32).digest()


def _host_batch(payloads):
    return [_blake(p) for p in payloads]


# -- (a) one dispatch + flush on the device engine ----------------------------

def test_one_batch_records_every_digest_stage_once(obs_enabled, monkeypatch):
    monkeypatch.setenv("DAT_DEVICE_HASH", "1")
    pipe = DigestPipeline(max_batch=4)
    got = []
    payloads = [b"stage-%d" % i for i in range(4)]  # one 1-block bucket
    for p in payloads:
        pipe.submit(p, got.append)     # the 4th submit dispatches
    pipe.flush()
    assert got == [_blake(p) for p in payloads]

    by_name = {n: SPANS.spans(n) for n in DIGEST_STAGES}
    assert {n: len(r) for n, r in by_name.items()} == \
        {n: 1 for n in DIGEST_STAGES}
    rec = {n: r[0] for n, r in by_name.items()}
    for n in DISPATCH_STAGES:
        assert rec[n]["parent"] == rec["digest.dispatch"]["id"], n
    for n in COLLECT_STAGES:
        assert rec[n]["parent"] == rec["digest.collect"]["id"], n
    # one identifier ties the batch's stages together
    assert {r["fields"]["batch"] for r in rec.values()} == {1}
    assert all(r["fields"]["src"] == "jax" for r in rec.values())
    assert rec["digest.dispatch"]["fields"]["items"] == 4
    assert rec["digest.dispatch"]["fields"]["bytes"] == \
        sum(map(len, payloads))
    for parent, kids in (("digest.dispatch", DISPATCH_STAGES),
                         ("digest.collect", COLLECT_STAGES)):
        assert sum(rec[k]["dur"] for k in kids) <= rec[parent]["dur"]
    # the same durations, as histogram sums a snapshot reader can take
    for n in DIGEST_STAGES:
        h = _hist(f"span.{n}.seconds")
        assert h["count"] == 1
        assert h["sum"] == pytest.approx(rec[n]["dur"])


@pytest.mark.parametrize("rounds", [1, 3])
def test_each_bucket_stages_once_and_ships_what_it_staged(obs_enabled,
                                                          monkeypatch, rounds):
    """ISSUE 27: a bucket is ONE staged array of raw words and one
    ``device_put``.  Per bucket still one ``digest.pack`` / ``.h2d`` /
    ``.launch``; ``device.h2d.bytes`` is the staged rows plus their
    lengths; every staging buffer is counted as a reuse or an alloc."""
    monkeypatch.setattr(blake2b_mod, "_STAGE_POOL",
                        blake2b_mod._StagePool(4 << 20))
    # three buckets a round: 1 block x 3 items, 2 blocks x 2, 8 blocks
    # x 1 — each at its slot's one declared row count, 1,024
    payloads = [b"a", b"b" * 128, b"", b"c" * 129, b"d" * 256, b"e" * 1000]
    staged = 1024 * (1 + 2 + 8) * 128
    lengths = 4 * 1024 * 3
    for _ in range(rounds):
        assert blake2b_mod.blake2b_batch(payloads) == _host_batch(payloads)
    for n in DISPATCH_STAGES:
        recs = SPANS.spans(n)
        assert len(recs) == 3 * rounds, n
        assert sorted(r["fields"]["nblocks"] for r in recs) == \
            sorted([1, 2, 8] * rounds)
        assert _hist(f"span.{n}.seconds")["count"] == 3 * rounds
    got = _counters()
    assert got["device.h2d.bytes"] == rounds * (staged + lengths)
    # collected before the next round, so each later round reuses all three
    assert got["digest.stage.alloc"] == 3
    assert got.get("digest.stage.reuse", 0) == 3 * (rounds - 1)


def test_batch_is_inherited_only_inside_a_span_that_carries_it(obs_enabled,
                                                               monkeypatch):
    monkeypatch.setattr(trace_mod, "_span_hists", {})  # first visits
    with trace_mod.span("outer.stage", batch=7, items=2):
        with trace_mod.span("inner.stage", items=1):
            pass
    with trace_mod.span("later.stage"):
        pass
    inner, = SPANS.spans("inner.stage")
    assert 0.0 <= inner["fields"].pop("cpu") <= inner["dur"]
    assert inner["fields"] == {"src": "jax", "items": 1, "batch": 7}
    assert "batch" not in SPANS.spans("later.stage")[0]["fields"]


# -- (b) one observation per batch --------------------------------------------

def test_private_pipeline_observes_each_clock_once_per_batch(obs_enabled):
    pipe = DigestPipeline(hash_batch=_host_batch, max_batch=2,
                          max_inflight=2)
    got = []
    for i in range(6):                         # three batches of two
        pipe.submit(b"p%d" % i, got.append)
    pipe.flush()
    assert len(got) == 6 and pipe.dispatches == 3
    for name in ("span.digest.dispatch.seconds",
                 "span.digest.collect.seconds",
                 "span.digest.deliver.seconds",
                 "digest.batch.fill_s", "digest.batch.residence_s"):
        assert _hist(name)["count"] == 3, name
    # a batch's residence holds its collect and its deliver
    assert _hist("digest.batch.residence_s")["sum"] >= \
        _hist("span.digest.collect.seconds")["sum"] \
        + _hist("span.digest.deliver.seconds")["sum"]
    assert [r["fields"]["batch"] for r in SPANS.spans("digest.deliver")] \
        == [1, 2, 3]


def test_two_session_hub_observes_each_clock_once_per_batch(obs_enabled):
    hub = ReplicationHub(hash_batch=_host_batch, max_batch=4,
                         linger_s=0.001)
    try:
        a, b = hub.register("a"), hub.register("b")
        got = {"a": [], "b": []}
        a.submit_many([b"a%d" % i for i in range(6)],
                      lambda tag, d: got["a"].append(tag))
        for i in range(6):
            b.submit(b"b%d" % i, lambda tag, d: got["b"].append(tag), i)
        a.flush()
        b.flush()
        assert got == {"a": list(range(6)), "b": list(range(6))}
        a.close()
        b.close()
    finally:
        hub.close()
    batches = _counters()["device.dispatch.batches"]
    assert batches >= 3                      # 12 items, at most 4 a batch
    # the fill clock is the hub's (started in the sessions' queues) and
    # the pipeline behind it observes no second one of its own
    for name in ("digest.batch.fill_s", "digest.batch.residence_s",
                 "span.digest.dispatch.seconds",
                 "span.digest.deliver.seconds"):
        assert _hist(name)["count"] == batches, name
    assert _hist("span.hub.submit.seconds")["count"] == batches
    assert _hist("span.hub.compose.seconds")["count"] >= batches
    assert _hist("span.hub.distribute.seconds")["count"] >= 1
    assert _hist("hub.dispatch.wait_s")["count"] >= 1
    # every run mark was consumed with its items
    assert not a._state.marks and not b._state.marks


def test_hub_fill_clock_starts_in_the_session_queue(obs_enabled):
    """An item that sat in a session's queue for a while before the
    dispatcher composed it reads that wait, not the pipeline's."""
    hub = ReplicationHub(hash_batch=_host_batch, max_batch=4,
                         linger_s=5.0)
    try:
        s = hub.register("slow")
        s.submit(b"only", lambda d: None)     # sits in the queue ...
        time.sleep(0.12)
        s.flush()                             # ... until the barrier
        s.close()
    finally:
        hub.close()
    fill = _hist("digest.batch.fill_s")
    assert fill["count"] == 1 and fill["sum"] >= 0.1


# -- (c) per-run counters keep per-item totals --------------------------------

def _through_private_pipeline(payloads, dec_emit):
    pipe = DigestPipeline(hash_batch=_host_batch, max_batch=8)
    for i, p in enumerate(payloads):
        pipe.submit(p, dec_emit, i)
    pipe.flush()


def _through_hub(payloads, dec_emit, many):
    hub = ReplicationHub(hash_batch=_host_batch, max_batch=8,
                         linger_s=0.001)
    try:
        s = hub.register("s")
        if many:
            s.submit_many(payloads, dec_emit)
        else:
            for i, p in enumerate(payloads):
                s.submit(p, dec_emit, i)
        s.flush()
        s.close()
    finally:
        hub.close()


@pytest.mark.parametrize("path", ["pipeline", "hub", "submit_many"])
def test_counter_totals_at_quiescence_are_per_item_totals(obs_enabled, path):
    from dat_replication_protocol_tpu import decode

    dec = decode(backend="tpu", pipeline=DigestPipeline(
        hash_batch=_host_batch))
    seen = []
    dec.on_digest(lambda kind, seq, d: seen.append(seq))
    payloads = [b"x" * (10 + i) for i in range(20)]
    if path == "pipeline":
        _through_private_pipeline(payloads, dec._emit_change_digest)
    else:
        _through_hub(payloads, dec._emit_change_digest,
                     many=path == "submit_many")
    assert seen == list(range(20))
    c = _counters()
    assert c["device.submit.items"] == 20
    assert c["device.submit.bytes"] == sum(map(len, payloads))
    assert c["decoder.digests"] == 20
    assert c["device.dispatch.batches"] >= 3


def test_sidecar_session_digest_counters_match_its_record(obs_enabled):
    """The whole served path, in process: what the session says it
    delivered is what the per-run counters add up to."""
    fed = {"done": False}

    def read_bytes(_n):
        if fed["done"]:
            return b""
        fed["done"] = True
        return SESSION_4

    out = sidecar.run_session(read_bytes, lambda data: None)
    assert out["ok"] and out["digests"] == 2
    c = _counters()
    assert c["decoder.digests"] == 2
    assert c["device.submit.items"] == 2


# -- (d) OBS.frames -----------------------------------------------------------

def _frames_after_session() -> int:
    fed = {"done": False}

    def read_bytes(_n):
        if fed["done"]:
            return b""
        fed["done"] = True
        return SESSION_4

    assert sidecar.run_session(read_bytes, lambda data: None)["ok"]
    return len(SPANS.spans("decoder.frame")) \
        + len(SPANS.spans("encoder.frame"))


def test_gate_without_frames_records_no_frame_instant(obs_enabled):
    obs_metrics.disable()
    assert not obs_metrics.OBS.frames
    obs_metrics.enable(frames=False)
    assert obs_metrics.OBS.on and not obs_metrics.OBS.frames
    assert _frames_after_session() == 0
    assert _counters()["decoder.digests"] == 2     # the rest is lit
    assert SPANS.spans("digest.dispatch")
    obs_metrics.enable()                           # as tests and DAT_OBS=1
    assert obs_metrics.OBS.frames
    assert _frames_after_session() >= 4            # 2 frames in, 2 out
    obs_metrics.enable(frames=False)               # never un-lights them
    assert obs_metrics.OBS.frames


_MAIN_AND_REPORT = (
    "import sys\n"
    "from dat_replication_protocol_tpu import sidecar\n"
    "from dat_replication_protocol_tpu.obs import metrics, tracing\n"
    "rc = sidecar.main(sys.argv[1:])\n"
    "sys.stderr.write('GATE %s %s %d\\n' % (metrics.OBS.on, "
    "metrics.OBS.frames, len(tracing.SPANS.spans('decoder.frame'))))\n"
    "sys.exit(rc)\n")


@pytest.mark.parametrize("flags, frames", [
    (["--stats-fd", "2", "--stats-interval", "60"], False),
    (["--obs-http", "0"], False),
    (["--stats-fd", "2", "--stats-interval", "60", "--trace-jsonl",
      "{tmp}/peer.jsonl"], True),
], ids=["stats-fd", "obs-http", "stats-fd+trace-jsonl"])
def test_sidecar_flags_light_what_their_reader_needs(tmp_path, flags,
                                                     frames):
    env = dict(os.environ, DAT_DEVICE_HASH="0")
    env.pop("DAT_OBS", None)
    flags = [f.replace("{tmp}", str(tmp_path)) for f in flags]
    proc = subprocess.run(
        [sys.executable, "-c", _MAIN_AND_REPORT, "--stdio", *flags],
        input=SESSION_4, capture_output=True, cwd=REPO, env=env,
        timeout=120)
    assert proc.returncode == 0, proc.stderr.decode()
    gate = [ln for ln in proc.stderr.decode().splitlines()
            if ln.startswith("GATE ")][-1].split()
    assert gate[1] == "True"
    assert gate[2] == str(frames)
    assert (int(gate[3]) > 0) == frames
    if frames:
        log = (tmp_path / "peer.jsonl").read_text()
        assert '"span": "decoder.frame"' in log
        assert '"span": "encoder.frame"' in log


# -- (e) the dark path of every new site --------------------------------------

def _codes(code):
    yield code
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            yield from _codes(c)


def _all_names(fn) -> set:
    """Every global and attribute name in a function's bytecode,
    closures included."""
    names = set()
    for code in _codes(fn.__code__):
        names |= set(code.co_names)
    return names


# every function that gained a stage site.  The site's dark half is the
# span() call and nothing else, so the function may name no clock and no
# histogram beyond the ones listed for it: those it had before (the
# pump's own) or reads in a lit-only branch (`if _OBS.on` / a `t0` that
# is None when dark), which the next tests pin
SITES = {
    blake2b_mod.blake2b_batch_begin: set(),
    DigestPipeline.dispatch: set(),
    tpu_backend.TpuDecoder._note_blob_bytes: set(),
    DigestPipeline._deliver_oldest: {"_monotonic", "_H_RESIDENCE"},
    pump_mod.recv_pump: {"_perf", "_H_NATIVE"},
    pump_mod._recv_spanned: set(),
    pump_mod._ReadAhead.next: set(),
}
CLOCKS = {"monotonic", "_monotonic", "perf_counter", "_perf", "time",
          "_histogram"}


@pytest.mark.parametrize("fn", list(SITES), ids=lambda f: f.__qualname__)
def test_new_sites_name_no_clock_or_histogram_of_their_own(fn):
    names = _all_names(fn)
    assert "span" in names
    timing = {n for n in names if n in CLOCKS or n.startswith("_H_")}
    assert timing <= SITES[fn], sorted(timing)


def _gated_on_obs(fn, target: str) -> bool:
    """``target`` is loaded only after an ``_OBS`` load, in bytecode
    order: the function's one gate test comes first."""
    seen_gate = False
    for ins in dis.get_instructions(fn):
        if ins.argval == "_OBS":
            seen_gate = True
        if ins.argval == target and not seen_gate:
            return False
    return True


def test_read_ahead_counters_sit_behind_the_gate():
    for lit_only in ("_M_RA_READY", "_M_RA_SLABS"):
        assert _gated_on_obs(pump_mod._ReadAhead.next, lit_only)


def test_bucket_note_sits_behind_the_gate():
    # the payload bytes a bucket hands the kernel are summed lit only
    assert _gated_on_obs(blake2b_mod.blake2b_batch_begin, "_BUCKETS")


def test_pipeline_clock_reads_sit_behind_the_gate():
    assert _gated_on_obs(DigestPipeline.submit, "_monotonic")
    for lit_only in ("fold_digest_tallies", "_H_RESIDENCE", "_monotonic"):
        assert _gated_on_obs(DigestPipeline._deliver_oldest, lit_only)
    # dispatch reads its clock in the lit helper alone
    assert "_monotonic" not in DigestPipeline.dispatch.__code__.co_names
    assert "_lit_dispatch" in DigestPipeline.dispatch.__code__.co_names
    assert "_monotonic" in DigestPipeline._lit_dispatch.__code__.co_names


def test_span_dark_is_the_bare_annotation(monkeypatch):
    """Gate off: span() hands back the bound factory's object, fields
    dropped; no ring record, no histogram, no clock."""
    assert not obs_metrics.OBS.on
    made = []

    class Bare:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace_mod, "_span_factory", Bare)
    before = len(SPANS.spans())
    hists = set(obs_metrics.snapshot()["histograms"])
    s = trace_mod.span("digest.pack", items=3, nblocks=1)
    assert type(s) is Bare and made == ["digest.pack"]
    with s:
        pass
    assert len(SPANS.spans()) == before
    assert set(obs_metrics.snapshot()["histograms"]) == hists
    code = trace_mod.span.__code__
    assert not {"monotonic", "time", "_histogram"} & set(code.co_names)


def test_hub_dark_turn_names_no_wait_clock():
    """The dispatcher's wait is timed in its lit branch alone, and the
    three hub stages are plain span() sites."""
    loop = hub_engine.ReplicationHub._dispatch_loop.__code__
    assert "_H_WAIT" not in loop.co_names
    assert "span" in loop.co_names
    wait = hub_engine.ReplicationHub._idle_wait_locked
    assert _gated_on_obs(wait, "_H_WAIT")
    hand_over = hub_engine.ReplicationHub._hand_over.__code__
    assert "_H_WAIT" not in hand_over.co_names
    consts = {c for turn in (loop, hand_over) for code in _codes(turn)
              for c in code.co_consts if isinstance(c, str)}
    assert {"hub.compose", "hub.submit", "hub.distribute"} <= consts


def test_edge_dark_twin_has_no_stage_site():
    dark = EdgeLoop._dark_turn.__code__
    assert "_annotation" not in dark.co_names and "span" not in dark.co_names
    # the shared per-session turns open them in their lit branches only
    for fn in (EdgeLoop._io_turn, EdgeLoop._sweep_one):
        names = {c for code in _codes(fn.__code__) for c in code.co_consts
                 if isinstance(c, str)}
        assert names & {"edge.read", "edge.hub_drain", "edge.tx"}
    assert trace_mod.annotation("edge.read").__class__ is \
        trace_mod._span_factory


# -- (f) ISSUE 37: digest.launch's children and the second clock --------------

LAUNCH_CHILDREN = ("digest.launch.program",)


def test_launch_children_nest_under_digest_launch(obs_enabled, monkeypatch):
    """One chip: the jit call is a child of ``digest.launch``, carries
    the batch's ordinal, and its wall seconds fit inside the parent's
    (what is left is the parent's self time: the pool's ``give`` and
    the bookkeeping)."""
    monkeypatch.setenv("DAT_DEVICE_HASH", "1")
    monkeypatch.setattr(trace_mod, "_span_hists", {})  # first visits
    pipe = DigestPipeline(max_batch=4)
    got = []
    payloads = [b"launch-%d" % i for i in range(4)]
    for p in payloads:
        pipe.submit(p, got.append)
    pipe.flush()
    assert got == [_blake(p) for p in payloads]
    launch, = SPANS.spans("digest.launch")
    kids = {n: SPANS.spans(n) for n in LAUNCH_CHILDREN}
    assert {n: len(r) for n, r in kids.items()} == \
        {n: 1 for n in LAUNCH_CHILDREN}
    for n, (rec,) in kids.items():
        assert rec["parent"] == launch["id"], n
        assert rec["fields"]["batch"] == launch["fields"]["batch"] == 1, n
        assert launch["ts"] <= rec["ts"], n
        assert _hist(f"span.{n}.seconds")["count"] == 1
        assert _hist(f"span.{n}.cpu_seconds")["count"] == 1
    assert sum(r[0]["dur"] for r in kids.values()) <= launch["dur"]


@pytest.mark.parametrize("devices", [None, 4])
def test_each_arm_launches_with_the_program_alone(devices, obs_enabled,
                                                  monkeypatch):
    """On one chip as over a mesh the lengths ride the one
    ``device_put`` and the digests are cut on the host: ``.program`` is
    the launch's one child, and the old ``.lengths`` / ``.slice``
    children observe nothing."""
    from dat_replication_protocol_tpu.parallel import mesh as pmesh

    monkeypatch.setattr(trace_mod, "_span_hists", {})  # first visits
    payloads = [b"mesh-%d" % i for i in range(6)]
    mesh = None if devices is None else pmesh.make_mesh(devices)
    assert blake2b_mod.blake2b_batch_begin(
        payloads, use_pallas=False, mesh=mesh)() == _host_batch(payloads)
    launch, = SPANS.spans("digest.launch")
    program, = SPANS.spans("digest.launch.program")
    assert program["parent"] == launch["id"]
    assert program["dur"] <= launch["dur"]
    assert not SPANS.spans("digest.launch.lengths")
    assert not SPANS.spans("digest.launch.slice")
    assert _hist("span.digest.launch.program.cpu_seconds")["count"] == 1
    # (the registry keeps a name once registered: counts, not names)
    for gone in ("lengths", "slice"):
        assert _hist(f"span.digest.launch.{gone}.seconds")["count"] == 0


def test_cpu_is_within_wall_on_every_span_and_every_pair(obs_enabled,
                                                         monkeypatch):
    """Batches through the served pipeline: the first visit of every
    stage takes the second clock and one visit in ``CPU_CLOCK_EVERY``
    after it; a record that carries ``cpu`` carries no more than its
    ``dur``; every ``span.*.seconds`` histogram has its ``cpu_seconds``
    twin, fed on those visits alone."""
    every = obs_metrics.CPU_CLOCK_EVERY
    rounds = 4 * every
    monkeypatch.setenv("DAT_DEVICE_HASH", "1")
    monkeypatch.setattr(trace_mod, "_span_hists", {})
    pipe = DigestPipeline(max_batch=2)
    got = []
    for i in range(2 * rounds):
        pipe.submit(b"pair-%d" % i, got.append)
    pipe.flush()
    assert len(got) == 2 * rounds
    recs = [r for r in SPANS.spans() if r["fields"].get("src") == "jax"]
    names = set(DIGEST_STAGES) | set(LAUNCH_CHILDREN)
    assert {r["span"] for r in recs} >= names
    for name in names:
        mine = [r for r in recs if r["span"] == name]
        assert len(mine) == rounds, name
        assert "cpu" in mine[0]["fields"], name    # the first visit
        clocked = [r for r in mine if "cpu" in r["fields"]]
        assert rounds // every - 2 <= len(clocked) <= rounds // every + 2
        for r in clocked:
            assert 0.0 <= r["fields"]["cpu"] <= r["dur"], name
        wall = _hist(f"span.{name}.seconds")
        cpu = _hist(f"span.{name}.cpu_seconds")
        assert wall["count"] == rounds and cpu["count"] == len(clocked)
        assert cpu["sum"] == pytest.approx(
            sum(r["fields"]["cpu"] for r in clocked))
        assert cpu["sum"] <= sum(r["dur"] for r in clocked)


def test_the_second_clock_is_taken_on_one_visit_in_a_few():
    """``cpu_clock_visit``: the first visit, one in ``CPU_CLOCK_EVERY``
    over any long run, and never on a fixed beat — eight sessions read
    in turn must not always clock the same one."""
    every = obs_metrics.CPU_CLOCK_EVERY
    picks = [n for n in range(100 * every)
             if obs_metrics.cpu_clock_visit(n)]
    assert picks[0] == 0
    assert 90 <= len(picks) <= 110
    gaps = {b - a for a, b in zip(picks, picks[1:])}
    assert len(gaps) > 1 and max(gaps) <= 2 * every
    for period in (2, 3, 4, 7, 8, 16):
        assert len({n % period for n in picks}) == period, period


def _sleep_20ms():
    time.sleep(0.020)


def _burn_20ms():
    # on the thread's own CPU clock, so that a loaded machine cannot
    # starve the loop of the CPU it is meant to use
    end = time.thread_time() + 0.020
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("body, least, most", [
    (_sleep_20ms, 0.0, 0.005),      # off its CPU: the wall moves alone
    (_burn_20ms, 0.015, None),      # on it: cpu follows, and stays
])                                  # inside the wall
def test_the_second_clock_tells_work_from_waiting(obs_enabled, monkeypatch,
                                                  body, least, most):
    monkeypatch.setattr(trace_mod, "_span_hists", {})  # a first visit
    with trace_mod.span("clock.probe"):
        body()
    rec, = SPANS.spans("clock.probe")
    cpu, wall = rec["fields"]["cpu"], rec["dur"]
    assert wall >= 0.019
    assert least <= cpu <= (wall if most is None else most)
    assert _hist("span.clock.probe.cpu_seconds")["sum"] == \
        pytest.approx(cpu)
    assert _hist("span.clock.probe.seconds")["sum"] == pytest.approx(wall)


def test_dark_spans_read_no_cpu_clock_and_register_no_twin(monkeypatch):
    """Gate off: ``span()`` is the bare annotation — no ``cpu_seconds``
    histogram comes to be and the CPU clock is never read."""
    assert not obs_metrics.OBS.on
    reads = []
    monkeypatch.setattr(trace_mod, "_thread_time",
                        lambda: reads.append(1) or 0.0)
    hists = set(obs_metrics.snapshot()["histograms"])
    s = trace_mod.span("dark.probe", items=1)
    assert type(s) is (trace_mod._span_factory
                       or trace_mod._bind_span_factory())
    with s:
        pass
    assert reads == []
    after = set(obs_metrics.snapshot()["histograms"])
    assert after == hists
    assert "span.dark.probe.cpu_seconds" not in after
    assert "_thread_time" not in trace_mod.span.__code__.co_names


@pytest.mark.parametrize("lit", [False, True])
def test_recv_fetch_reads_the_cpu_clock_only_when_lit(request, monkeypatch,
                                                      lit):
    """The receive half's pair of clocks: dark, one gate check and no
    CPU clock; lit, ``pump.fetch.seconds`` on every receive — also the
    one that found nothing — and on the receives that take the second
    clock (the first of them here) two reads of it inside the wall
    clock's and one ``pump.fetch.cpu_seconds`` observation."""
    if lit:
        request.getfixturevalue("obs_enabled")
    assert obs_metrics.OBS.on is lit
    monkeypatch.setenv("DAT_PUMP", "native")
    reads = []
    real = time.thread_time
    monkeypatch.setattr(pump_mod, "_thread_time",
                        lambda: reads.append(1) or real())
    monkeypatch.setattr(pump_mod, "_fetches", itertools.count())
    before = {n: _hist(n)["count"]
              for n in ("pump.fetch.seconds", "pump.fetch.cpu_seconds")}
    a, b = socket.socketpair()
    b.setblocking(False)
    try:
        ep = pump_mod.EdgePump(b.fileno(), cap=1 << 16)
        if not ep.native:
            pytest.skip("no native pump library on this machine")
        a.sendall(b"\x00" * 4096)
        for want in (4096, -11):    # a slab, then would-block
            _buf, r, seconds = pump_mod.recv_fetch(ep)
            assert r[0] == want and seconds >= 0.0
    finally:
        a.close()
        b.close()
    # of two receives the first takes the second clock (visits 0, 1)
    assert len(reads) == (2 if lit else 0)
    wall, cpu = (_hist(n) for n in before)
    assert wall["count"] - before["pump.fetch.seconds"] == (2 if lit else 0)
    assert cpu["count"] - before["pump.fetch.cpu_seconds"] == \
        (1 if lit else 0)
    if lit:
        assert 0.0 <= cpu["sum"] <= wall["sum"]


def test_backend_opens_no_second_span_system_at_its_sites():
    src = inspect.getsource(tpu_backend)
    assert '"device.dispatch"' not in src and '"device.deliver"' not in src
    assert "_trace_span" not in src
    assert '"device.dispatch.batches"' in src      # the counter stays


def test_obs_discipline_is_clean():
    r = subprocess.run(
        [sys.executable, "-m", "dat_replication_protocol_tpu.analysis"],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
