"""The one general traffic generator: closed-loop sessions over a socket.

A traffic file (`benchmarks/traffic/<name>.json`) names this module
under `"generator"` and gives nothing but parameters:

    item            {"kind": "blob", "bytes": N}  or
                    {"kind": "change", "value_bytes": N, "key_prefix": S,
                     "key_distribution": "zipfian" | "uniform",
                     "zipfian_constant": C, "key_space": K}
    clients         concurrent clients, each its own connection at a time
    processes       client processes the clients are dealt over
    session_items   items in one session; null = ONE session per client
                    that streams for the whole window (closed by the
                    socket's backpressure), ending on a multiple of
                    `session_multiple` items so that only whole batches
                    reach the device
    pool_items      distinct items each client makes from the seed and
                    cycles through (a multiple of session_items)
    warmup          {"lone_sessions": [n, ...], "loop_seconds": s}
    trace_slice_s   length of the profiler slice in a traced run
    dry_run         overrides for `--dry-run` (tiny sizes, CPU rehearsal)

Everything a session sends, and every digest it must get back, is made
here from the seed BEFORE the window: the window holds no rng, no
encoding of items and no hashlib call.  Every seed gives the same
sizes; only contents and key order change.

Nothing here imports the package under test.
"""

from __future__ import annotations

import functools
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from reference import digests as ref  # noqa: E402

SEND_CHUNK = 1 << 18

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def fnv64(values: np.ndarray) -> np.ndarray:
    """YCSB's `Utils.fnvhash64` over an array: FNV-1a of the eight
    little-endian octets of each value, sign dropped as YCSB does."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * _FNV_PRIME
            v = v >> np.uint64(8)
    return np.abs(h.astype(np.int64)).astype(np.uint64)


@functools.lru_cache(maxsize=4)
def zeta(space: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, space + 1, dtype=np.float64)
                        ** theta))


def zipfian_ranks(rng, count: int, space: int, theta: float) -> np.ndarray:
    """YCSB's `ZipfianGenerator.nextLong` (Gray et al., "Quickly
    generating billion-record synthetic databases"), vectorised."""
    zetan = zeta(space, theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / space) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(count)
    uz = u * zetan
    ranks = (space * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks[uz < zeta2] = 1
    ranks[uz < 1.0] = 0
    return np.minimum(ranks, space - 1)


def change_keys(rng, item: dict, count: int) -> list[str]:
    space = int(item["key_space"])
    dist = item.get("key_distribution", "uniform")
    if dist == "zipfian":
        ranks = zipfian_ranks(rng, count, space,
                              float(item["zipfian_constant"]))
        # YCSB's ScrambledZipfianGenerator spreads the hot ranks over
        # the key space; `insertorder=hashed` then names the key
        keynums = fnv64(ranks) % np.uint64(space)
    elif dist == "uniform":
        keynums = rng.integers(0, space, size=count).astype(np.uint64)
    else:
        raise ValueError(f"unknown key_distribution {dist!r}")
    prefix = item.get("key_prefix", "user")
    return [f"{prefix}{h}" for h in fnv64(keynums).tolist()]


class Pool:
    """One client's distinct items: whole frames, the digest each must
    be answered with, and each payload's length."""

    def __init__(self, params: dict, seed: int, client: int):
        item = params["item"]
        self.kind = item["kind"]
        n = int(params["pool_items"])
        rng = np.random.default_rng([int(seed), 7, int(client)])
        type_id = ref.FRAME_TYPE[self.kind]
        if self.kind == "blob":
            size = int(item["bytes"])
            payloads = [rng.bytes(size) for _ in range(n)]
        else:
            size = int(item["value_bytes"])
            raw = rng.bytes(size * n)
            keys = change_keys(rng, item, n)
            payloads = [
                ref.change_payload(keys[i], i, i, i + 1,
                                   raw[i * size:(i + 1) * size])
                for i in range(n)]
        self.digests = [ref.digest(p) for p in payloads]
        self.payload_len = [len(p) for p in payloads]
        self.frames = [ref.frame_header(len(p), type_id) + p
                       for p in payloads]

    def __len__(self) -> int:
        return len(self.frames)


class Plan:
    """What one session sends and what it must receive.

    wire side:  `chunk()` is the next bytes to send, `advance(n)` takes
                n of them as sent; `frame_ends[i]` is the wire offset at
                which item i is wholly sent.
    reply side: `exp` is the canonical reply stream so far, `exp_ends[i]`
                the offset at which reply i ends, `want(i)` the fields a
                differently encoded reply i must carry.
    """

    kind: str
    frame_ends: list
    pay_cum: list
    exp: bytes | bytearray
    exp_ends: list

    def want(self, i: int):
        raise NotImplementedError


class FinitePlan(Plan):
    """`count` consecutive pool items from `start`: one immutable
    template, built in set-up and shared by every session that repeats
    it; a session keeps only a cursor (`Cursor`)."""

    def __init__(self, pool: Pool, start: int, count: int):
        idx = [(start + j) % len(pool) for j in range(count)]
        self.kind = pool.kind
        self.wire = b"".join(pool.frames[i] for i in idx)
        self.frame_ends = np.cumsum(
            [len(pool.frames[i]) for i in idx]).tolist()
        self.pay_cum = [0] + np.cumsum(
            [pool.payload_len[i] for i in idx]).tolist()
        self.digests = [pool.digests[i] for i in idx]
        replies = [ref.expected_reply(self.kind, j, d)
                   for j, d in enumerate(self.digests)]
        self.exp = b"".join(replies)
        self.exp_ends = np.cumsum([len(r) for r in replies]).tolist()
        self.count = count

    def want(self, i: int):
        if i >= self.count:
            return None
        return self.kind, i, self.digests[i]

    def cursor(self) -> "Cursor":
        return Cursor(self)


class Cursor(Plan):
    """A session's place in a FinitePlan."""

    def __init__(self, plan: FinitePlan):
        self.plan = plan
        self.kind = plan.kind
        self.frame_ends = plan.frame_ends
        self.pay_cum = plan.pay_cum
        self.exp = plan.exp
        self.exp_ends = plan.exp_ends
        self.want = plan.want
        self._view = memoryview(plan.wire)
        self._pos = 0

    def chunk(self, now: float):
        if self._pos >= len(self._view):
            return None
        return self._view[self._pos:self._pos + SEND_CHUNK]

    def advance(self, n: int) -> None:
        self._pos += n


class EndlessPlan(Plan):
    """One session that cycles through the pool until `stop_at`, then
    on to the next multiple of `multiple` items.  The expected reply
    stream grows as items are handed out (varints only, no hashing)."""

    def __init__(self, pool: Pool, multiple: int, stop_at: float):
        self.pool = pool
        self.kind = pool.kind
        self.multiple = max(1, int(multiple))
        self.stop_at = stop_at
        self.frame_ends: list = []
        self.pay_cum: list = [0]
        self.exp = bytearray()
        self.exp_ends: list = []
        self._view = None
        self._off = 0
        self._wire = 0

    def want(self, i: int):
        if i >= len(self.frame_ends):
            return None
        return self.kind, i, self.pool.digests[i % len(self.pool)]

    def chunk(self, now: float):
        if self._view is None:
            n = len(self.frame_ends)
            if now >= self.stop_at and n % self.multiple == 0 and n:
                return None
            k = n % len(self.pool)
            frame = self.pool.frames[k]
            self._view, self._off = memoryview(frame), 0
            self._wire += len(frame)
            self.frame_ends.append(self._wire)
            self.pay_cum.append(self.pay_cum[-1] + self.pool.payload_len[k])
            self.exp += ref.expected_reply(self.kind, n,
                                           self.pool.digests[k])
            self.exp_ends.append(len(self.exp))
        return self._view[self._off:]

    def advance(self, n: int) -> None:
        self._off += n
        if self._off >= len(self._view):
            self._view = None



class ClientTraffic:
    """All one client sends: its pool and the session plans over it."""

    def __init__(self, params: dict, seed: int, client: int):
        self.params = params
        self.pool = Pool(params, seed, client)
        self.session_items = params.get("session_items")
        self._next = 0
        self.plans: list[FinitePlan] = []
        if self.session_items:
            n, per = len(self.pool), int(self.session_items)
            if n % per:
                raise ValueError("pool_items must be a multiple of "
                                 "session_items")
            self.plans = [FinitePlan(self.pool, at, per)
                          for at in range(0, n, per)]

    def next_session(self, stop_at: float):
        """The next session of the closed loop."""
        if not self.session_items:
            return EndlessPlan(self.pool,
                               self.params.get("session_multiple", 1),
                               stop_at)
        plan = self.plans[self._next % len(self.plans)]
        self._next += 1
        return plan.cursor()

    def lone_session(self, count: int):
        """A warm-up session of `count` items from the pool's start."""
        return FinitePlan(self.pool, 0, count).cursor()
