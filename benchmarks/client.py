#!/usr/bin/env python3
"""One load-generator process: some of a cell's clients on one
single-threaded event loop.

    python benchmarks/client.py '<json spec>'

Started by `run.py`, one process per `processes` of the traffic file, so
that no client shares an interpreter with the measurement or with the
sidecar.  It makes its clients' traffic from the seed at once (while the
sidecar is still starting), prints `ready`, then obeys one-line JSON
commands on stdin, answering each with one JSON line on stdout:

    {"cmd": "sessions", "port": P, "counts": [n, ...]}
        client 0 of this process runs lone sessions, one after another
    {"cmd": "loop", "port": P, "t0": T, "t1": T, "out": PATH}
        every client runs the closed loop from monotonic time t0 until
        t1, lets what is in flight finish, and writes the timings to PATH

Every reply byte of every session is compared with the reference
(`reference/digests.py`): first against the canonical reply stream, and
where that differs, frame by frame and field by field.  Timestamps are
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
from bisect import bisect_right

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from reference import digests as ref  # noqa: E402

DRAIN_LIMIT_S = 150.0


def load_generator(name: str):
    path = os.path.join(HERE, "generators", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no generator {path}")
    spec = importlib.util.spec_from_file_location(f"generators_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Conn:
    """One session on one non-blocking socket."""

    def __init__(self, plan, sock):
        self.plan = plan
        self.sock = sock
        self.wpos = 0            # wire bytes sent
        self.sent = 0            # items wholly sent
        self.sent_t: list = []   # marks: (time, items wholly sent)
        self.sent_n: list = []
        self.rpos = 0            # reply bytes matched against plan.exp
        self.got = 0             # digests received and compared
        self.recv_t: list = []   # marks: (time, digests received)
        self.recv_n: list = []
        self.rbuf = None         # set once the reply left the canonical form
        self.bad = 0
        self.first_bad = None
        self.t_first = None
        self.t_end = None
        self.write_open = True
        self.error = None

    # -- sending -----------------------------------------------------------

    def on_writable(self) -> None:
        chunk = self.plan.chunk(time.monotonic())
        if chunk is None:
            self.sock.shutdown(socket.SHUT_WR)
            self.write_open = False
            return
        if self.t_first is None:
            self.t_first = time.monotonic()
        try:
            n = self.sock.send(chunk)
        except BlockingIOError:
            return
        t = time.monotonic()
        self.plan.advance(n)
        self.wpos += n
        done = bisect_right(self.plan.frame_ends, self.wpos)
        if done > self.sent:
            self.sent = done
            self.sent_t.append(t)
            self.sent_n.append(done)

    # -- receiving and comparing ------------------------------------------

    def on_readable(self) -> bool:
        """False once the reply stream has ended."""
        try:
            data = self.sock.recv(1 << 20)
        except BlockingIOError:
            return True
        t = time.monotonic()
        if not data:
            self.t_end = t
            return False
        before = self.got
        self._compare(data)
        if self.got > before:
            self.recv_t.append(t)
            self.recv_n.append(self.got)
        return True

    def _compare(self, data: bytes) -> None:
        plan = self.plan
        if self.rbuf is None:
            end = self.rpos + len(data)
            if memoryview(plan.exp)[self.rpos:end] == data:
                self.rpos = end
                self.got = bisect_right(plan.exp_ends, end)
                return
            # not the canonical bytes: parse from the last frame boundary
            # (what matched so far equals the canonical stream)
            at = plan.exp_ends[self.got - 1] if self.got else 0
            self.rbuf = bytearray(plan.exp[at:self.rpos])
        self.rbuf += data
        pos = 0
        while True:
            try:
                parsed = ref.parse_frame(self.rbuf, pos)
            except ValueError as e:
                self._bad(f"reply does not parse: {e}")
                self.rbuf.clear()
                return
            if parsed is None:
                break
            type_id, fields, pos = parsed
            want = plan.want(self.got)
            if want is None:
                self._bad(f"reply {self.got} to an item never sent")
            else:
                fault = ref.reply_fault(type_id, fields, *want)
                if fault:
                    self._bad(fault)
            self.got += 1
        del self.rbuf[:pos]

    def _bad(self, what: str) -> None:
        self.bad += 1
        self.first_bad = self.first_bad or what

    # -- the verdict -------------------------------------------------------

    def close(self, error=None) -> None:
        if error is not None and self.error is None:
            self.error = error
        if self.t_end is None:
            self.t_end = time.monotonic()
        self.sock.close()

    def verdict(self) -> dict:
        """Items attempted and failed under the configuration's
        guarantees: one correct digest per item, in order, and the reply
        ended after the last one, not before and not inside a frame."""
        attempted = len(self.plan.frame_ends)
        ends = self.plan.exp_ends
        matched = ends[self.got - 1] if 0 < self.got <= len(ends) else 0
        fault = None
        if self.error is not None:
            fault = f"{type(self.error).__name__}: {self.error}"
        elif self.write_open:
            fault = "reply ended before the session had sent everything"
        elif self.bad:
            fault = self.first_bad
        elif self.got == 0 and attempted:
            fault = "reply ended with no digest: rejected or shed"
        elif self.got != attempted:
            fault = f"{self.got} digests for {attempted} items"
        elif self.rbuf or (self.rbuf is None and self.rpos != matched):
            fault = "reply ended inside a frame"
        good = min(attempted, max(0, self.got - self.bad))
        failed = 0
        if fault:
            # a session that broke a guarantee with every digest right
            # (a torn end) fails whole
            failed = attempted - good or attempted
        return {"attempted": attempted, "failed": failed,
                "compared": self.got, "fault": fault}


def run_sessions(next_plan, slots: int, port: int, t0: float,
                 t1: float) -> list[Conn]:
    """The closed loop: each of `slots` clients opens a session, and a
    new one when its reply has ended, until t1; then what is in flight
    finishes.  `next_plan(slot)` gives a slot's next session, or None
    when it has no more."""
    sel = selectors.DefaultSelector()
    done: list[Conn] = []
    live = 0

    def start(slot: int) -> None:
        nonlocal live
        plan = next_plan(slot)
        if plan is None:
            return
        sock = socket.create_connection(("127.0.0.1", port), timeout=60)
        sock.setblocking(False)
        conn = Conn(plan, sock)
        sel.register(conn.sock, selectors.EVENT_READ | selectors.EVENT_WRITE,
                     (slot, conn))
        live += 1

    def finish(slot: int, conn: Conn, error=None) -> None:
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


def report(conns: list[Conn], out: str | None) -> dict:
    """Counts on the reply line; per-delivery and per-item timings in
    an .npz beside it (too many for a line)."""
    verdicts = [c.verdict() for c in conns]
    rep = {
        "sessions": len(conns),
        "attempted": sum(v["attempted"] for v in verdicts),
        "failed": sum(v["failed"] for v in verdicts),
        "compared": sum(v["compared"] for v in verdicts),
        "bad_sessions": sum(1 for v in verdicts if v["fault"]),
        "first_fault": next((v["fault"] for v in verdicts if v["fault"]),
                            None),
        # of the sessions that were wholly right: what pad_share and
        # the kernel's byte count take the mean payload from
        "ok_items": sum(c.got for c, v in zip(conns, verdicts)
                        if not v["fault"]),
        "ok_payload_bytes": sum(c.plan.pay_cum[c.got]
                                for c, v in zip(conns, verdicts)
                                if not v["fault"]),
    }
    if out is None:
        return rep
    d_t, d_items, d_bytes, i_t, i_lag, s_t0, s_t1 = [], [], [], [], [], [], []
    for c, v in zip(conns, verdicts):
        if v["fault"] or not c.got:
            continue   # only sessions that were wholly right are timed
        recv_t = np.asarray(c.recv_t)
        recv_n = np.asarray(c.recv_n)
        pay = np.asarray(c.plan.pay_cum)[recv_n]
        d_t.append(recv_t)
        d_items.append(np.diff(recv_n, prepend=0))
        d_bytes.append(np.diff(pay, prepend=0))
        item = np.arange(c.got)
        t_recv = recv_t[np.searchsorted(recv_n, item, side="right")]
        t_sent = np.asarray(c.sent_t)[
            np.searchsorted(np.asarray(c.sent_n), item, side="right")]
        i_t.append(t_recv)
        i_lag.append(t_recv - t_sent)
        s_t0.append(c.t_first)
        s_t1.append(c.t_end)

    def cat(parts, dtype=np.float64):
        return np.concatenate(parts) if parts else np.zeros(0, dtype)

    np.savez(out, delivery_t=cat(d_t), delivery_items=cat(d_items, np.int64),
             delivery_bytes=cat(d_bytes, np.int64), item_t=cat(i_t),
             item_lag=cat(i_lag), session_t0=np.asarray(s_t0, np.float64),
             session_t1=np.asarray(s_t1, np.float64))
    return rep


def main(argv: list[str]) -> int:
    spec = json.loads(argv[1])
    gen = load_generator(spec["generator"])
    t = time.monotonic()
    clients = [gen.ClientTraffic(spec["traffic"], spec["seed"], cid)
               for cid in spec["clients"]]
    print(json.dumps({"ready": True, "clients": len(clients),
                      "made_s": time.monotonic() - t}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "sessions":
            counts = list(cmd["counts"])

            def lone(slot, counts=counts):
                if slot or not counts or not clients:
                    return None
                return clients[0].lone_session(counts.pop(0))

            now = time.monotonic()
            # t1 far off: the one slot keeps opening sessions until the
            # list is empty
            conns = run_sessions(lone, 1, cmd["port"], now, now + 3600.0)
            rep = report(conns, None)
        elif cmd["cmd"] == "loop":
            t1 = cmd["t1"]
            conns = run_sessions(
                lambda slot: clients[slot].next_session(t1)
                if time.monotonic() < t1 else None,
                len(clients), cmd["port"], cmd["t0"], t1)
            rep = report(conns, cmd.get("out"))
        else:
            rep = {"error": f"unknown command {cmd['cmd']!r}"}
        print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
