#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json, measured at the client's
end of the socket.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1> [--dry-run]

This parent never imports jax.  It starts the sidecar (`serve.py`, the
one process that owns the chip) and the load-generator clients
(`client.py`), warms up the cell's own shapes, measures for `--seconds`,
lets what is in flight finish, and prints a report whose LAST line is
one JSON object (README.md says what is on the earlier lines).

`--trace 0`: the sidecar runs dark (no telemetry flag); the metrics are
the cell's end-to-end metrics, all on the clients' clock.
`--trace 1`: the sidecar gets `--stats-fd`/`--obs-http`, a profiler
slice of the traffic file's `trace_slice_s` seconds is taken inside the
window, and the metrics are the cell's per-layer metrics.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name BENCHMARK.json gives:
`configs/<name>.json` (the manifest's `file`), `traffic/<name>.json`,
`generators/<generator>.py`, `layer_metrics/<name>.py`.  There is no
`if workload == …` here.

`--dry-run` rehearses the control flow on the CPU at the traffic file's
`dry_run` sizes; its last line says `"dry_run": true` and carries no
metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
# readers import their shared `_stats` helper by name
sys.path.insert(0, os.path.join(HERE, "layer_metrics"))

import metrics as arithmetic  # noqa: E402
from procs import BenchFailure, Client, Sidecar, cpu_seconds  # noqa: E402

START_LIMIT_S = 600.0        # sidecar start; the first run builds and compiles
WARMUP_LIMIT_S = 900.0
TRACE_START_LIMIT_S = 60.0
TRACE_STOP_LIMIT_S = 200.0   # printed before the wait; PR 22 died on a silent one
REDUCE_LIMIT_S = 200.0
DRAIN_LIMIT_S = 170.0        # the clients give up at 150 s themselves
SLICE_DELAY_S = 1.0          # into the window, so the slice is steady state


def say(*a) -> None:
    print("bench:", *a, flush=True)


def need_file(path: str, what: str) -> str:
    if not os.path.isfile(path):
        raise BenchFailure(f"no {what}: {path} is missing")
    return path


def load_json(path: str, what: str) -> dict:
    with open(need_file(path, what)) as f:
        return json.load(f)


def load_reader(name: str):
    path = need_file(os.path.join(HERE, "layer_metrics", f"{name}.py"),
                     f"reader for the per-layer metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def for_cell(metrics: list, cell: str) -> list:
    """The manifest's metrics this cell reports (no `workloads` key:
    every cell)."""
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def resolve(manifest: dict, cell_name: str, dry: bool) -> dict:
    """Every file the cell needs, by the names in the manifest."""
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == cell_name), None)
    if cell is None:
        raise BenchFailure(
            f"no workload {cell_name!r} in BENCHMARK.json: it has "
            f"{[w['name'] for w in manifest['workloads']]}")
    entry = next((c for c in manifest["configs"]
                  if c["name"] == cell["config"]), None)
    if entry is None:
        raise BenchFailure(f"no configuration {cell['config']!r} in "
                           f"BENCHMARK.json")
    config = load_json(os.path.join(ROOT, entry["file"]),
                       f"file of configuration {entry['name']!r}")
    traffic = load_json(
        os.path.join(HERE, "traffic", f"{cell['traffic']}.json"),
        f"file of traffic mix {cell['traffic']!r}")
    if dry:
        traffic = {**traffic, **traffic.get("dry_run", {})}
    need_file(os.path.join(HERE, "generators",
                           f"{traffic['generator']}.py"),
              f"generator {traffic['generator']!r}")
    need_file(os.path.join(ROOT, config["reference"]), "plain reference")
    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": for_cell(manifest["end_to_end"], cell_name),
        "per_layer": [(m, load_reader(m["name"]))
                      for m in for_cell(manifest["per_layer"], cell_name)],
    }


class CpuSampler(threading.Thread):
    """CPU seconds of the children at the window's two ends, read on
    time even while the parent waits on a profiler stop."""

    def __init__(self, pids: dict, t0: float, t1: float):
        super().__init__(daemon=True)
        self.pids, self.at = pids, (t0, t1)
        self.samples: list[dict] = []

    def run(self) -> None:
        for t in self.at:
            time.sleep(max(0.0, t - time.monotonic()))
            now = time.monotonic()
            self.samples.append(
                {"t": now, **{k: cpu_seconds(p)
                              for k, p in self.pids.items()}})

    def cores(self) -> dict:
        a, b = self.samples
        return {k: (b[k] - a[k]) / (b["t"] - a["t"]) for k in self.pids}


def check_device(dev: dict, config: dict, chips: int, dry: bool) -> None:
    """Refuse before a byte is served: anything but a TPU with the
    engine the configuration file names is not this benchmark."""
    if dry:
        return
    if dev.get("platform") != "tpu":
        raise BenchFailure(
            f"no accelerator: the sidecar resolved {dev}; nothing was "
            f"served")
    if dev.get("engine") != config["engine"]:
        raise BenchFailure(
            f"configuration wants engine {config['engine']!r}, the "
            f"sidecar resolved {dev}")
    if dev.get("device_count", 0) < chips:
        raise BenchFailure(
            f"the cell asks for {chips} chips, jax holds "
            f"{dev.get('device_count')}")


def merge_counts(reports: list[dict], into: dict) -> None:
    for r in reports:
        if "error" in r:
            raise BenchFailure(f"a client process reported {r['error']}")
        for k in ("sessions", "attempted", "failed", "compared",
                  "bad_sessions", "ok_items", "ok_payload_bytes"):
            into[k] = into.get(k, 0) + r[k]
        if r["first_fault"] and not into.get("first_fault"):
            into["first_fault"] = r["first_fault"]


def on_all(clients: list, cmd_for, timeout: float, what: str) -> list:
    tickets = [c.send(cmd_for(i)) for i, c in enumerate(clients)]
    return [c.answer(t, timeout, what) for c, t in zip(clients, tickets)]


def reduce_trace(trace_dir: str, env: dict) -> tuple[dict, int]:
    """Read the slice in a child of its own, now that the sidecar (the
    chip's owner) has exited."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise BenchFailure(f"the profiler wrote no .xplane.pb under "
                           f"{trace_dir}")
    size = os.path.getsize(files[0])
    cmd = [sys.executable, os.path.join(HERE, "reduce_trace.py"), files[0]]
    dump_dir = os.environ.get("BENCH_TRACE_DUMP_DIR")
    if dump_dir:    # shake-down only: keep what the reduction saw
        os.makedirs(dump_dir, exist_ok=True)
        cmd += ["--dump", os.path.join(dump_dir, "trace_dump.json")]
    try:
        r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           env={**env, "JAX_PLATFORMS": "cpu"},
                           timeout=REDUCE_LIMIT_S)
    except subprocess.TimeoutExpired:
        raise BenchFailure(f"reducing the {size}-byte trace took over "
                           f"{REDUCE_LIMIT_S:.0f} s") from None
    if r.returncode:
        raise BenchFailure("reduce_trace.py failed:\n" + r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1]), size


def run(args, plan: dict, workdir: str) -> dict:
    cell, config, traffic = plan["cell"], plan["config"], plan["traffic"]
    trace = bool(args.trace) and not args.dry_run
    env = dict(os.environ)
    if args.dry_run:
        env["JAX_PLATFORMS"] = "cpu"
    n_proc = int(traffic["processes"])
    n_clients = int(traffic["clients"])
    say(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {int(trace)} dry_run {int(args.dry_run)}; host cpus "
        f"{os.cpu_count()}; {n_clients} clients over {n_proc} processes")

    sc = Sidecar(list(config["sidecar_flags"]), telemetry=trace, env=env)
    clients: list[Client] = []
    counts: dict = {}
    try:
        for i in range(n_proc):
            clients.append(Client({
                "generator": traffic["generator"], "traffic": traffic,
                "seed": args.seed,
                "clients": list(range(i, n_clients, n_proc))}, env))
        dev_line = sc.wait_listening(START_LIMIT_S)
        say(sc.device_line)
        check_device(dev_line, config, int(cell["chips"]), args.dry_run)
        start_s = sc.t_listening - sc.t_spawn
        made = [c.answer(0, START_LIMIT_S, "its traffic") for c in clients]
        port = sc.port

        # -- warm-up: the cell's own shapes, every session compared ------
        t_warm = time.monotonic()
        warm = traffic.get("warmup", {})
        if warm.get("lone_sessions"):
            t = clients[0].send({"cmd": "sessions", "port": port,
                                 "counts": warm["lone_sessions"]})
            merge_counts([clients[0].answer(t, WARMUP_LIMIT_S,
                                            "the lone warm-up sessions")],
                         counts)
        if warm.get("loop_seconds"):
            w0 = time.monotonic() + 0.2
            merge_counts(on_all(
                clients, lambda i: {"cmd": "loop", "port": port, "t0": w0,
                                    "t1": w0 + warm["loop_seconds"]},
                WARMUP_LIMIT_S, "the warm-up loop"), counts)
        if counts.get("failed"):
            raise BenchFailure(
                f"warm-up: {counts['failed']} of {counts['attempted']} "
                f"items without a correct digest, first: "
                f"{counts.get('first_fault')}")
        say(f"set-up: sidecar start {start_s:.2f} s; clients made their "
            f"traffic in {max(m['made_s'] for m in made):.2f} s (beside "
            f"it); warm-up {time.monotonic() - t_warm:.2f} s "
            f"({counts.get('sessions', 0)} sessions, "
            f"{counts.get('compared', 0)} digests compared)")

        # -- the window ---------------------------------------------------
        t0 = time.monotonic() + 0.5
        t1 = t0 + args.seconds
        setup_s = t0 - T_PROCESS
        outs = [os.path.join(workdir, f"timings{i}.npz")
                for i in range(n_proc)]
        tickets = [c.send({"cmd": "loop", "port": port, "t0": t0, "t1": t1,
                           "out": outs[i]})
                   for i, c in enumerate(clients)]
        sampler = CpuSampler(
            {"sidecar": sc.proc.pid,
             **{f"client{i}": c.proc.pid for i, c in enumerate(clients)}},
            t0, t1)
        sampler.start()
        slice_rep = None
        if trace:
            slice_s = float(traffic["trace_slice_s"])
            trace_dir = os.path.join(workdir, "trace")
            time.sleep(max(0.0, t0 + SLICE_DELAY_S - time.monotonic()))
            a = sc.ask({"cmd": "trace_start", "dir": trace_dir},
                       TRACE_START_LIMIT_S)
            time.sleep(max(0.0, a["t"] + slice_s - time.monotonic()))
            say(f"slice: {slice_s:g} s traced; stopping the profiler, "
                f"waiting at most {TRACE_STOP_LIMIT_S:.0f} s")
            try:
                b = sc.ask({"cmd": "trace_stop"}, TRACE_STOP_LIMIT_S)
            except BenchFailure as e:
                raise BenchFailure(
                    f"the profiler's stop exceeded its limit or failed "
                    f"({e}); the slice was {slice_s:g} s") from None
            slice_rep = {"slice_s": b["t"] - a["t"], "stop_s": b["stop_s"]}
        time.sleep(max(0.0, t1 - time.monotonic()))
        reports = [c.answer(t, DRAIN_LIMIT_S + args.seconds,
                            "the clients' report of the window")
                   for c, t in zip(clients, tickets)]
        sampler.join(10)
        window: dict = {}
        merge_counts(reports, window)
        merge_counts(reports, counts)

        # -- after the window ---------------------------------------------
        device = sc.ask({"cmd": "device"}, 60.0)
        snaps = None
        if trace:
            sc.wait_for(lambda: any(s.get("monotonic", 0) >= t1
                                 for s in sc.snapshots), 30.0,
                     "a stats snapshot after the window")
            snaps = (sc.snapshot_near(t0), sc.snapshot_near(t1))
    except BenchFailure as e:
        raise BenchFailure(f"{e}\n-- sidecar stderr, last lines --\n"
                           f"{sc.tail()}") from None
    finally:
        for c in clients:
            c.stop()
        sc.stop()

    timings = {}
    parts = [np.load(p) for p in outs]
    for k in parts[0].files:
        timings[k] = np.concatenate([p[k] for p in parts])
    e2e, notes = arithmetic.end_to_end(timings, t0, t1)
    e2e["setup_s"] = setup_s
    cores = sampler.cores()
    reduced = None
    if trace:
        reduced, size = reduce_trace(trace_dir, env)
        slice_rep["xplane_bytes"] = size
    return {"t0": t0, "t1": t1, "seconds": args.seconds, "counts": counts,
            "window": window, "e2e": e2e, "notes": notes, "cores": cores,
            "device": device, "device_line": dev_line, "snaps": snaps,
            "trace": reduced, "slice": slice_rep, "timings": timings,
            "traffic": traffic, "config": config}


def report(args, plan: dict, res: dict) -> int:
    """The earlier lines, then the JSON line, last."""
    counts, notes, cores = res["counts"], res["notes"], res["cores"]
    client_cores = [v for k, v in cores.items() if k != "sidecar"]
    say(f"compared {counts['compared']} digests of {counts['sessions']} "
        f"sessions (warm-up included) with "
        f"{plan['config']['reference']}: {counts['failed']} of "
        f"{counts['attempted']} items failed, {counts['bad_sessions']} "
        f"sessions broke a guarantee"
        + (f", first: {counts['first_fault']}"
           if counts.get("first_fault") else ""))
    say("window: " + json.dumps(
        {k: notes[k] for k in (
            "deliveries", "items", "first_s", "last_s", "span_s",
            "sessions_in_window",
            "digest_lag_samples", "digest_lag_p50_ms", "session_p50_ms")}))
    say(f"cpu in the window: sidecar {cores['sidecar']:.3f} cores; "
        f"{len(client_cores)} client processes "
        f"{sum(client_cores):.3f} cores together, the busiest "
        f"{max(client_cores):.3f} (near 1.0 the cell measures the "
        f"generator)")
    for w in notes["warnings"]:
        say("warning:", w)
    say("end to end: " + json.dumps(res["e2e"]))
    correct = (counts["failed"] == 0 and counts["attempted"] > 0
               and counts["compared"] == counts["attempted"])
    head = {"correct": correct, "attempted": counts["attempted"],
            "failed": counts["failed"]}
    if args.dry_run:
        print(json.dumps({**head, "dry_run": True,
                          "workload": plan["cell"]["name"],
                          "sessions": counts["sessions"],
                          "compared": counts["compared"]}), flush=True)
        return 0 if correct else 1

    dev = res["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": dev["memory_peak_bytes"]}
    out_metrics: dict = {}
    line = {**head, "metrics": out_metrics, "device": device}
    if not args.trace:
        for m in plan["end_to_end"]:
            v = res["e2e"].get(m["name"])
            if v is None:
                raise BenchFailure(
                    f"end-to-end metric {m['name']!r} could not be "
                    f"measured: window notes {notes}")
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        tr, sl = res["trace"], res["slice"]
        say(f"slice: {sl['slice_s']:.3f} s traced, stop took "
            f"{sl['stop_s']:.2f} s, .xplane.pb {sl['xplane_bytes']} "
            f"bytes; {tr['host_spans']} host spans; programs "
            f"{json.dumps(tr['programs'])}; longest idle gap "
            f"{tr['longest_gap_s']:.4f} s")
        say("trace lines seen: " + json.dumps(tr["seen"]))
        peaks = load_json(os.path.join(HERE, "peaks.json"), "table of peaks")
        if dev["kind"] not in peaks:
            raise BenchFailure(
                f"device_kind {dev['kind']!r} is not in peaks.json: add "
                f"its published peaks with their source")
        ctx = {**res, "peaks": peaks[dev["kind"]]}
        for m, read in plan["per_layer"]:
            v = read(ctx)
            if v is not None:     # nothing to read: left out of the line
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr["busy_s"] <= 0:
            raise BenchFailure("no operation ran on the device inside "
                               "the profiler slice")
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--dry-run", action="store_true")
    args = p.parse_args(argv)
    workdir = tempfile.mkdtemp(prefix="datbench-")
    try:
        manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"),
                             "manifest")
        if args.seconds is None:
            args.seconds = float(manifest["run_seconds"])
        plan = resolve(manifest, args.workload, args.dry_run)
        if args.dry_run:
            args.seconds = min(args.seconds, float(
                plan["traffic"].get("seconds", args.seconds)))
        return report(args, plan, run(args, plan, workdir))
    except BenchFailure as e:
        say("FAILED:", e)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
