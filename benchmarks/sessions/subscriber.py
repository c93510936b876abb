"""The fan-out subscriber: one connection a client for the whole window.
It sends nothing, never half-closes (the sidecar reads a half-close as
the peer leaving and frees its slot) and reads the broadcast until EOF.

Its reference is the writer's wire: the session that the group it reads
(`"reads"` in the traffic file: a group of one client) makes with its
generator from the same seed.  Every byte received is compared with it,
from byte 0: the byte compare of replicas.  The comparison pulls that
session from the writer's own plan as far as a reader has got, as views
of the generator's frames, so no copy of the broadcast is made.

A subscriber cannot know where the writer's session ends (it ends on a
whole batch after the window), so `Conn.verdict` says what one
connection saw, and `settle` holds each connection's length against the
writer's once both groups have reported (`run.py` calls it).  A verdict
fails on a refusal record, a byte that differs, no bytes at all or a
timeout; `settle` adds fewer (or more) bytes than the writer sent.

The kind's hooks are those `sessions/digest.py` lists; `lone_plan` gives
nothing (a warm-up session would take the broadcast's source slot).

Nothing here imports the package under test.
"""

from __future__ import annotations

import math
import os
import sys
import time
from bisect import bisect_right

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from sessions import _refusal  # noqa: E402

RECV_BYTES = 4 << 20
HEAD_BYTES = 4096        # kept to name a refusal record


class Wire:
    """The writer's session as its generator makes it: the wire bytes
    (`chunk` / `advance`) and the frame ends (`frame_ends`), pulled from
    its plan as far as a reader needs."""

    def __init__(self, plan):
        self.plan = plan
        self.starts: list = []   # wire offset of each piece
        self.pieces: list = []   # uint8 views of the generator's frames
        self.end = 0
        self.ended = False       # the plan has no more bytes

    def _pull(self, upto: int) -> None:
        while self.end < upto and not self.ended:
            chunk = self.plan.chunk(-math.inf)
            if chunk is None:
                self.ended = True
                break
            self.starts.append(self.end)
            self.pieces.append(np.frombuffer(chunk, np.uint8))
            self.end += len(chunk)
            self.plan.advance(len(chunk))

    def equal_prefix(self, at: int, data: np.ndarray) -> int:
        """How many leading bytes of `data` equal the wire from `at`."""
        n = len(data)
        self._pull(at + n)
        i = bisect_right(self.starts, at) - 1
        done = 0
        while done < n and 0 <= i < len(self.pieces):
            piece = self.pieces[i]
            off = at + done - self.starts[i]
            take = min(n - done, len(piece) - off)
            got, want = data[done:done + take], piece[off:off + take]
            if not np.array_equal(got, want):
                return done + int(np.argmax(got != want))
            done += take
            i += 1
        return done     # short of n: the wire ended first

    def frames_within(self, nbytes: int) -> int:
        """Writer frames wholly inside the first `nbytes` of the wire
        (pulled already)."""
        return bisect_right(self.plan.frame_ends, nbytes)


class Clients:
    """The group's subscribers in this process, sharing one reference:
    the session the writer's generator makes for the writer's client."""

    def __init__(self, gen, spec: dict):
        source = gen.ClientTraffic(spec["traffic"], spec["seed"],
                                   spec["source_client"])
        self.wire = Wire(source.next_session(math.inf))
        self.n = len(spec["clients"])
        self.given: set = set()

    def __len__(self) -> int:
        return self.n

    def next_plan(self, slot: int, t1: float):
        """One connection a slot: after its EOF the broadcast is over."""
        if slot in self.given:
            return None
        self.given.add(slot)
        return self.wire

    def lone_plan(self, count: int):
        return None


class Conn:
    """One subscriber connection on one non-blocking socket."""

    def __init__(self, wire: Wire, sock):
        self.plan = wire
        self.sock = sock
        self.write_open = False  # it never sends
        self.buf = bytearray(RECV_BYTES)
        self.head = bytearray()
        self.received = 0        # bytes received
        self.matched = 0         # leading bytes equal to the writer's wire
        self.first_bad = None    # offset of the first byte that differs
        self.frames = 0          # writer frames wholly received, all equal
        self.frame_t: list = []  # marks: (time, frames wholly received)
        self.frame_n: list = []
        self.recv_t: list = []   # every receive: time and bytes
        self.recv_n: list = []
        self.t_first = None
        self.t_end = None
        self.error = None

    def on_readable(self) -> bool:
        """False once the broadcast has ended (EOF)."""
        try:
            n = self.sock.recv_into(self.buf)
        except BlockingIOError:
            return True
        t = time.monotonic()
        if not n:
            self.t_end = t
            return False
        self.take(np.frombuffer(self.buf, np.uint8, n), t)
        return True

    def take(self, data: np.ndarray, t: float) -> None:
        """Compare one receive's bytes with the writer's wire."""
        if self.t_first is None:
            self.t_first = t
        if len(self.head) < HEAD_BYTES:
            self.head += data[:HEAD_BYTES - len(self.head)].tobytes()
        self.recv_t.append(t)
        self.recv_n.append(len(data))
        self.received += len(data)
        if self.first_bad is not None:
            return
        equal = self.plan.equal_prefix(self.matched, data)
        self.matched += equal
        if equal < len(data):
            self.first_bad = self.matched
        frames = self.plan.frames_within(self.matched)
        if frames > self.frames:
            self.frames = frames
            self.frame_t.append(t)
            self.frame_n.append(frames)

    def close(self, error=None) -> None:
        if error is not None and self.error is None:
            self.error = error
        if self.t_end is None:
            self.t_end = time.monotonic()
        self.sock.close()

    def verdict(self) -> dict:
        """What this connection saw; its items are counted by `settle`,
        against the writer's."""
        refused = _refusal.record(self.head)
        fault = None
        if self.error is not None:
            fault = f"{type(self.error).__name__}: {self.error}"
        elif refused:
            fault = refused
        elif self.first_bad is not None:
            fault = (f"byte {self.first_bad} differs from the writer's "
                     f"wire")
        elif not self.received:
            fault = "the broadcast ended with no byte received"
        return {"attempted": 0, "failed": 0, "compared": 0, "fault": fault,
                "received": self.received, "matched": self.matched,
                "frames": self.frames}


def judge(peer: dict, wire_bytes: int, wire_items: int) -> dict:
    """One connection held to the writer's whole session: `wire_items`
    frames in `wire_bytes` bytes, every one received and equal."""
    fault = peer["fault"]
    if fault is None and peer["received"] != wire_bytes:
        fault = (f"{peer['received']} bytes received of the writer's "
                 f"{wire_bytes}: shed or cut short"
                 if peer["received"] < wire_bytes else
                 f"{peer['received']} bytes received, the writer sent "
                 f"{wire_bytes}")
    if fault is None and peer["frames"] != wire_items:
        fault = f"{peer['frames']} frames of the writer's {wire_items}"
    return {"attempted": wire_items,
            "failed": wire_items if fault else 0,
            "compared": min(peer["frames"], wire_items), "fault": fault}


def settle(rep: dict, source: dict) -> dict:
    """This group's counts once the group it reads has reported."""
    judged = [judge(p, source["wire_bytes"], source["attempted"])
              for p in rep["peers"]]
    faults = [j["fault"] for j in judged if j["fault"]]
    return {**rep,
            "attempted": sum(j["attempted"] for j in judged),
            "failed": sum(j["failed"] for j in judged),
            "compared": sum(j["compared"] for j in judged),
            "bad_sessions": len(faults),
            "first_fault": faults[0] if faults else None}


def counts(conns: list, verdicts: list) -> dict:
    return {"peers": [{k: v[k] for k in ("received", "matched", "frames",
                                         "fault")} for v in verdicts],
            "compared_bytes": sum(v["matched"] for v in verdicts)}


def arrays(conns: list, verdicts: list) -> dict:
    """For each writer frame a subscriber received whole, the time its
    last byte came; and every receive's time and bytes."""
    f_t, f_k, r_t, r_n = [], [], [], []
    for c, v in zip(conns, verdicts):
        if v["fault"]:
            continue
        r_t.append(np.asarray(c.recv_t, np.float64))
        r_n.append(np.asarray(c.recv_n, np.int64))
        k = np.arange(c.frames)
        f_t.append(np.asarray(c.frame_t, np.float64)[
            np.searchsorted(np.asarray(c.frame_n), k, side="right")])
        f_k.append(k)

    def cat(parts, dtype=np.float64):
        return np.concatenate(parts) if parts else np.zeros(0, dtype)

    return {"sub_frame_t": cat(f_t), "sub_frame_k": cat(f_k, np.int64),
            "sub_recv_t": cat(r_t), "sub_recv_bytes": cat(r_n, np.int64)}
