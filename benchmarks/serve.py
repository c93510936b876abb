#!/usr/bin/env python3
"""The sidecar child: the process that owns the chip.

    python benchmarks/serve.py <sidecar flags>

Runs `dat_replication_protocol_tpu.sidecar.main(argv)` on the main
thread, unchanged, so its own signal handling works.  Beside it one
control thread obeys one-line JSON commands on stdin, each answered by
one line `ctl: <json>` on stdout (the `--tcp` sidecar uses neither):

    {"cmd": "device"}              what jax holds, and its peak memory
    {"cmd": "trace_start", "dir"}  start jax.profiler here, host tracers
                                   turned down
    {"cmd": "trace_stop"}          stop it; the answer says how long the
                                   stop took

Only the process that holds the chip can trace it, and the sidecar has
no profiler flag: this thread is that flag, kept with the benchmark.
With `--trace 0` the parent sends `device` once, after the window, and
nothing else.  When stdin ends (the parent is gone) the process ends.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# PR 22 paid 8 s of stop per traced second at jax 0.9.0's defaults
# (python_tracer_level 1, host_tracer_level 2): seventeen
# `futex-default-*` host lines of ~165,000 events each
PYTHON_TRACER_LEVEL = 0
HOST_TRACER_LEVEL = 1


def answer(**fields) -> None:
    sys.stdout.write("ctl: " + json.dumps(fields) + "\n")
    sys.stdout.flush()


def device_record() -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def trace_start(log_dir: str) -> dict:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = PYTHON_TRACER_LEVEL
    opts.host_tracer_level = HOST_TRACER_LEVEL
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    # the slice's edges on the trace's own clock
    with jax.profiler.TraceAnnotation("bench.slice.begin"):
        pass
    return {"t": time.monotonic()}


def trace_stop() -> dict:
    import jax

    with jax.profiler.TraceAnnotation("bench.slice.end"):
        pass
    t = time.monotonic()
    jax.profiler.stop_trace()
    return {"t": t, "stop_s": time.monotonic() - t}


COMMANDS = {"device": lambda cmd: device_record(),
            "trace_start": lambda cmd: trace_start(cmd["dir"]),
            "trace_stop": lambda cmd: trace_stop()}


def control() -> None:
    for line in sys.stdin:
        try:
            cmd = json.loads(line)
            answer(cmd=cmd["cmd"], ok=True, **COMMANDS[cmd["cmd"]](cmd))
        except Exception as e:  # noqa: BLE001 — the parent is told, always
            answer(cmd=line.strip()[:80], ok=False,
                   error=f"{type(e).__name__}: {e}")
    os.kill(os.getpid(), signal.SIGTERM)


def main(argv: list[str]) -> int:
    sys.path.insert(0, ROOT)
    from dat_replication_protocol_tpu import sidecar

    threading.Thread(target=control, name="bench-control",
                     daemon=True).start()
    return sidecar.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
