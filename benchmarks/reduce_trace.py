#!/usr/bin/env python3
"""From a profiler slice to the few numbers the benchmark keeps.

    python benchmarks/reduce_trace.py <file.xplane.pb> [--dump OUT.json]

Run by `run.py` as a child of its own AFTER the sidecar has exited:
reading the file needs jax (`jax.profiler.ProfileData`), and no process
but the sidecar may touch jax while the sidecar holds the chip.  Prints
one JSON object: `reduce()` of the file.

Two steps, so that the arithmetic can be tested on a small recorded
fixture without jax:

    load(path) -> dump    plain lists out of the .xplane.pb: per device
                          plane the lines `XLA Modules` (one event per
                          program run) and `XLA Ops`; the program's host
                          spans from `/host:CPU`; the slice's two marks
    reduce(dump) -> dict  busy and idle, time per program, the device
                          operations by time, the idle gaps by the host
                          span that covered them.  Nothing else.

Kernels are found by PROGRAM name (`jit_blake2b_packed_pallas`): the
`pl.pallas_call`s have no stable `name=` yet, and show as
`%blake2b_native.N … custom-call` inside it (PERF.md, Open questions).
"""

from __future__ import annotations

import heapq
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
LINES = ("XLA Modules", "XLA Ops")
# the program's own spans (`utils/trace.span` -> TraceAnnotation) are
# dotted lower-case names; the runtime's own host events are not
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
MARK_BEGIN, MARK_END = "bench.slice.begin", "bench.slice.end"
TOP = 10
NAME_MAX = 120


def _columns(events) -> dict:
    names: dict = {}
    idx, start, dur = [], [], []
    for name, s, d in events:
        idx.append(names.setdefault(name, len(names)))
        start.append(s)
        dur.append(d)
    return {"names": list(names), "idx": idx, "start_ns": start,
            "dur_ns": dur}


def _rows(col: dict | None):
    """(name, start, duration) rows of a column group; none for None."""
    if not col:
        return []
    names = col["names"]
    return [(names[i], s, d) for i, s, d in
            zip(col["idx"], col["start_ns"], col["dur_ns"])]


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dump: dict = {"devices": [], "host_spans": None, "marks": {},
                  "seen": {}}
    host = []
    for plane in data.planes:
        seen = dump["seen"].setdefault(plane.name, {})
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        dev = {"plane": plane.name}
        for line in plane.lines:
            events = list(line.events)
            seen[line.name] = seen.get(line.name, 0) + len(events)
            if is_dev and line.name in LINES:
                dev[line.name] = _columns(
                    (e.name, e.start_ns, e.duration_ns) for e in events)
            elif plane.name == HOST_PLANE:
                for e in events:
                    if e.name == MARK_BEGIN:
                        dump["marks"]["begin_ns"] = e.start_ns
                    elif e.name == MARK_END:
                        dump["marks"]["end_ns"] = e.start_ns
                    elif SPAN_NAME.match(e.name):
                        host.append((e.name, e.start_ns, e.duration_ns))
        if is_dev:
            dump["devices"].append(dev)
    dump["devices"].sort(key=lambda d: d["plane"])
    dump["host_spans"] = _columns(host)
    return dump


def union(intervals) -> list:
    """Sorted, disjoint [start, end) from any intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        elif e > s:
            out.append([s, e])
    return out


def innermost(spans, w0: float, w1: float) -> list:
    """Flatten spans that nest and overlap (threads) into disjoint
    (start, end, name) segments, each named by the covering span that
    started last."""
    points = []
    for i, (name, s, d) in enumerate(spans):
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            points.append((s, 1, i))
            points.append((e, 0, i))
    points.sort()
    out, heap, ended = [], [], set()
    prev = None
    for t, is_start, i in points:
        while heap and heap[0][1] in ended:
            heapq.heappop(heap)
        if heap and prev is not None and t > prev:
            out.append((prev, t, spans[heap[0][1]][0]))
        if is_start:
            heapq.heappush(heap, (-spans[i][1], i))
        else:
            ended.add(i)
        prev = t
    return out


def short_op(name: str) -> str:
    """`%copy.2 = u32[…]{…} copy(u32[…] %bitcast.2)` -> `%copy.2 copy`:
    an HLO line is hundreds of characters, a breakdown row is a name."""
    lhs, sep, rhs = name.partition(" = ")
    if sep:
        m = re.search(r"(?<![A-Za-z0-9_])([a-z][a-z0-9_\-]*)\(", rhs)
        name = f"{lhs} {m.group(1)}" if m else lhs
    return name[:NAME_MAX]


def program_name(name: str) -> str:
    """`jit_blake2b_packed_pallas(12762634282938574503)` without the
    fingerprint, which changes with every edit of the program."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(dump: dict) -> dict:
    devices = dump["devices"]
    if not devices:
        raise ValueError("no /device:TPU:N plane in the trace: planes "
                         f"seen {sorted(dump.get('seen', {}))}")
    marks = dump.get("marks") or {}
    ops0 = _rows(devices[0].get("XLA Ops"))
    host = _rows(dump.get("host_spans"))
    if "begin_ns" in marks and "end_ns" in marks:
        w0, w1 = marks["begin_ns"], marks["end_ns"]
    elif dump.get("host_extent_ns"):        # the PR 22 recording
        w0, w1 = dump["host_extent_ns"]
    else:
        every = [(s, s + d) for _, s, d in ops0 + host]
        if not every:
            raise ValueError("an empty trace")
        w0, w1 = min(s for s, _ in every), max(e for _, e in every)
    if w1 <= w0:
        raise ValueError(f"slice marks out of order: {marks}")

    def clipped(rows):
        """(name, start, end) of the rows inside the slice, cut to it."""
        return [(n, max(s, w0), min(s + d, w1)) for n, s, d in rows
                if s + d > w0 and s < w1]

    busy_ns = []
    for dev in devices:
        rows = clipped(_rows(dev.get("XLA Ops")))
        busy_ns.append(sum(e - s for s, e in
                           union((s, e) for _, s, e in rows)))
    programs: dict = {}
    for name, s, e in clipped(_rows(devices[0].get("XLA Modules"))):
        row = programs.setdefault(program_name(name), [0.0, 0])
        row[0] += (e - s) / 1e9
        row[1] += 1
    by_op: dict = {}
    for name, s, e in clipped(ops0):
        key = short_op(name)
        by_op[key] = by_op.get(key, 0.0) + (e - s) / 1e9

    # idle gaps of the first device, by what the host was doing
    busy = union((s, e) for _, s, e in clipped(ops0))
    gaps, at = [], w0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if w1 > at:
        gaps.append((at, w1))
    by_span: dict = {}
    segs = innermost(host, w0, w1)
    j = 0
    for g0, g1 in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            s, e, name = segs[k]
            part = min(e, g1) - max(s, g0)
            if part > 0:
                by_span[name] = by_span.get(name, 0.0) + part / 1e9
                covered += part
            k += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            by_span["no span"] = by_span.get("no span", 0.0) + rest / 1e9

    def top(table: dict) -> list:
        return [[k, v] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "devices": len(devices),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "programs": programs,
        "device_ops": top(by_op),
        "idle_gaps": top(by_span),
        "longest_gap_s": max((g1 - g0 for g0, g1 in gaps), default=0) / 1e9,
        "host_spans": len(host),
    }


def main(argv: list[str]) -> int:
    dump = load(argv[1])
    if "--dump" in argv:
        with open(argv[argv.index("--dump") + 1], "w") as f:
            json.dump(dump, f)
    out = reduce(dump)
    out["seen"] = dump["seen"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
