"""The digest session: a client sends items and holds the reply to one
digest record per item.

A session kind is a file `sessions/<kind>.py` that `client.py` finds by
the name a traffic file gives under `"session"` (no key: this one).  It
gives

    Clients(gen, spec)      the group's clients in one process: `len()`,
                            `next_plan(slot, t1)` (the slot's next
                            session, or None when it has no more) and
                            `lone_plan(count)` (a warm-up session)
    Conn(plan, sock)        one session on one non-blocking socket:
                            `write_open`, `on_writable()` (asked only
                            while `write_open`), `on_readable()` (False
                            once the stream ended), `close(error)`,
                            `verdict()`
    counts(conns, verdicts) the kind's counts on the process's reply line
    arrays(conns, verdicts) the kind's timing arrays, saved to the .npz

Every reply byte is compared with the reference (`reference/digests.py`):
first against the canonical reply stream, and where that differs, frame
by frame and field by field.

Nothing here imports the package under test.
"""

from __future__ import annotations

import os
import socket
import sys
import time
from bisect import bisect_right

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from reference import digests as ref  # noqa: E402
from sessions import _refusal  # noqa: E402


class Clients:
    """One generator traffic per client of the group in this process."""

    def __init__(self, gen, spec: dict):
        self.clients = [gen.ClientTraffic(spec["traffic"], spec["seed"], cid)
                        for cid in spec["clients"]]

    def __len__(self) -> int:
        return len(self.clients)

    def next_plan(self, slot: int, t1: float):
        return self.clients[slot].next_session(t1)

    def lone_plan(self, count: int):
        return self.clients[0].lone_session(count)


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
            refused = None if self.rpos else _refusal.record(data)
            if refused:   # a refusal record in place of the reply
                self._bad(refused)
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
            fault = ("reply ended before the session had sent everything"
                     + (f": {self.first_bad}" if self.bad else ""))
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


def counts(conns: list, verdicts: list) -> dict:
    return {
        # of the sessions that were wholly right: what pad_share and
        # the kernel's byte count take the mean payload from
        "ok_items": sum(c.got for c, v in zip(conns, verdicts)
                        if not v["fault"]),
        "ok_payload_bytes": sum(c.plan.pay_cum[c.got]
                                for c, v in zip(conns, verdicts)
                                if not v["fault"]),
        # what a reader of this group's broadcast is held against
        "wire_bytes": sum(c.wpos for c in conns),
    }


def arrays(conns: list, verdicts: list) -> dict:
    """Per-delivery and per-item timings of the sessions that were
    wholly right."""
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

    return {"delivery_t": cat(d_t), "delivery_items": cat(d_items, np.int64),
            "delivery_bytes": cat(d_bytes, np.int64), "item_t": cat(i_t),
            "item_lag": cat(i_lag),
            "session_t0": np.asarray(s_t0, np.float64),
            "session_t1": np.asarray(s_t1, np.float64)}
