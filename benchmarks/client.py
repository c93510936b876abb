#!/usr/bin/env python3
"""One load-generator process: some of one client group's clients on one
single-threaded event loop.

    python benchmarks/client.py '<json spec>'

Started by `run.py`, one process per `processes` of the group, so that
no client shares an interpreter with the measurement or with the
sidecar.  The spec names the group's session kind (`sessions/<kind>.py`,
found by name), its generator, its parameters, the seed and the clients
this process holds.  It makes its clients' traffic from the seed at once
(while the sidecar is still starting), prints `ready`, then obeys
one-line JSON commands on stdin, answering each with one JSON line on
stdout:

    {"cmd": "sessions", "port": P, "counts": [n, ...]}
        client 0 of this process runs lone sessions, one after another
    {"cmd": "connect", "port": P}
        every client opens its first connection now and sends nothing
        (a traffic file with client groups: run.py connects the groups
        in order, then gives them one t0)
    {"cmd": "loop", "port": P, "t0": T, "t1": T, "out": PATH}
        every client runs the closed loop from monotonic time t0 until
        t1 (on the connection it opened, where it did), lets what is in
        flight finish, and writes the timings to PATH

The kind decides what a session sends, what it must receive and how it
is judged (`sessions/digest.py` lists its hooks).  Timestamps are
`time.monotonic()`, which all processes of one host share.

Nothing here imports the package under test.
"""

from __future__ import annotations

import importlib.util
import json
import os
import selectors
import socket
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

DRAIN_LIMIT_S = 150.0


def _load(folder: str, name: str, what: str):
    path = os.path.join(HERE, folder, f"{name}.py")
    if name.startswith("_") or not os.path.isfile(path):
        raise FileNotFoundError(f"no {what} {path}")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_generator(name: str):
    return _load("generators", name, "generator")


def load_session(kind: str):
    """The session kind `sessions/<kind>.py`."""
    return _load("sessions", kind, "session kind")


def connect(port: int) -> socket.socket:
    sock = socket.create_connection(("127.0.0.1", port), timeout=60)
    sock.setblocking(False)
    return sock


def run_sessions(conn_type, next_plan, slots: int, port: int, t0: float,
                 t1: float, opened: dict | None = None) -> list:
    """The closed loop: each of `slots` clients opens a session, and a
    new one when its stream has ended, until t1; then what is in flight
    finishes.  `next_plan(slot)` gives a slot's next session, or None
    when it has no more; `conn_type(plan, sock)` is the kind's
    connection.  A slot's first session takes its socket from `opened`
    where the slot has one there (and sends nothing before t0)."""
    opened = dict(opened or {})
    sel = selectors.DefaultSelector()
    done: list = []
    live = 0

    def start(slot: int) -> None:
        nonlocal live
        plan = next_plan(slot)
        if plan is None:
            return
        sock = opened.pop(slot, None) or connect(port)
        conn = conn_type(plan, sock)
        sel.register(conn.sock, selectors.EVENT_READ
                     | (selectors.EVENT_WRITE if conn.write_open else 0),
                     (slot, conn))
        live += 1

    def finish(slot: int, conn, error=None) -> None:
        nonlocal live
        sel.unregister(conn.sock)
        conn.close(error)
        done.append(conn)
        live -= 1
        if time.monotonic() < t1 and error is None:
            start(slot)

    delay = t0 - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    for slot in range(slots):
        start(slot)
    for sock in opened.values():    # slots that had no session to run
        sock.close()
    deadline = t1 + DRAIN_LIMIT_S
    while live:
        if time.monotonic() > deadline:
            for key in list(sel.get_map().values()):
                slot, conn = key.data
                finish(slot, conn, TimeoutError(
                    f"in flight {DRAIN_LIMIT_S:.0f} s after the window"))
            break
        for key, mask in sel.select(0.05):
            slot, conn = key.data
            try:
                if mask & selectors.EVENT_READ and not conn.on_readable():
                    finish(slot, conn)
                    continue
                if mask & selectors.EVENT_WRITE and conn.write_open:
                    conn.on_writable()
                    if not conn.write_open:
                        sel.modify(conn.sock, selectors.EVENT_READ,
                                   (slot, conn))
            except OSError as e:
                finish(slot, conn, e)
    sel.close()
    return done


def report(kind, conns: list, out: str | None) -> dict:
    """Counts on the reply line; the kind's timings in an .npz beside it
    (too many for a line)."""
    verdicts = [c.verdict() for c in conns]
    rep = {
        "sessions": len(conns),
        "attempted": sum(v["attempted"] for v in verdicts),
        "failed": sum(v["failed"] for v in verdicts),
        "compared": sum(v["compared"] for v in verdicts),
        "bad_sessions": sum(1 for v in verdicts if v["fault"]),
        "first_fault": next((v["fault"] for v in verdicts if v["fault"]),
                            None),
        **kind.counts(conns, verdicts),
    }
    if out is not None:
        np.savez(out, **kind.arrays(conns, verdicts))
    return rep


def main(argv: list[str]) -> int:
    spec = json.loads(argv[1])
    kind = load_session(spec.get("session", "digest"))
    gen = load_generator(spec["generator"])
    t = time.monotonic()
    clients = kind.Clients(gen, spec)
    print(json.dumps({"ready": True, "clients": len(clients),
                      "made_s": time.monotonic() - t}), flush=True)
    opened: dict = {}
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "sessions":
            counts = list(cmd["counts"])

            def lone(slot, counts=counts):
                if slot or not counts or not len(clients):
                    return None
                return clients.lone_plan(counts.pop(0))

            now = time.monotonic()
            # t1 far off: the one slot keeps opening sessions until the
            # list is empty
            conns = run_sessions(kind.Conn, lone, 1, cmd["port"], now,
                                 now + 3600.0)
            rep = report(kind, conns, None)
        elif cmd["cmd"] == "connect":
            opened = {slot: connect(cmd["port"])
                      for slot in range(len(clients))}
            rep = {"connected": len(opened)}
        elif cmd["cmd"] == "loop":
            t1 = cmd["t1"]
            conns = run_sessions(
                kind.Conn, lambda slot: clients.next_plan(slot, t1)
                if time.monotonic() < t1 else None,
                len(clients), cmd["port"], cmd["t0"], t1, opened)
            opened = {}
            rep = report(kind, conns, cmd.get("out"))
        else:
            rep = {"error": f"unknown command {cmd['cmd']!r}"}
        print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
