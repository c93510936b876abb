"""Closed-loop traffic of blobs whose sizes follow a file-size
distribution: a folder of files shared as one stream of blobs.

A traffic file names this module under `"generator"` and gives the
parameters of `closed_loop.py` (see there), with `item` given as

    {"kind": "blob",
     "sizes": {"distribution": "lognormal", "mu": M, "sigma": S,
               "max_bytes": CAP}}

`M` and `S` in ln bytes.  The pool's `pool_items` files take the
distribution's quantiles at (i + 0.5) / pool_items, rounded to whole
bytes and clipped at CAP.  `session_items` is null: one session per
client streams for the whole window and ends on a multiple of
`session_multiple` files.

The seed shuffles the pool's order and draws the files' contents; the
pool is then cycled in that order.  Every seed sends the same sizes, so
a heavy tail cannot make one seed's window heavier than another's.  As
in `closed_loop.py`, everything a session sends and every digest it
must get back is made from the seed before the window, and nothing here
imports the package under test.
"""

from __future__ import annotations

import os
import sys
from statistics import NormalDist

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from generators import closed_loop  # noqa: E402
from reference import digests as ref  # noqa: E402


def quantile_sizes(sizes: dict, count: int) -> np.ndarray:
    """The `count` quantiles of the size distribution, whole bytes,
    clipped at `max_bytes`: the same for every seed."""
    if sizes["distribution"] != "lognormal":
        raise ValueError(f"unknown distribution {sizes['distribution']!r}")
    z = NormalDist().inv_cdf
    ln = [float(sizes["mu"]) + float(sizes["sigma"]) * z((i + 0.5) / count)
          for i in range(count)]
    return np.minimum(np.rint(np.exp(ln)), int(sizes["max_bytes"])) \
        .astype(np.int64)


class Pool:
    """One client's files: the quantile sizes in a seeded order, each a
    whole frame, with the digest it must be answered with."""

    kind = "blob"

    def __init__(self, params: dict, seed: int, client: int):
        item = params["item"]
        if item["kind"] != self.kind:
            raise ValueError("this generator sends blobs")
        rng = np.random.default_rng([int(seed), 41, int(client)])
        sizes = rng.permutation(
            quantile_sizes(item["sizes"], int(params["pool_items"])))
        type_id = ref.FRAME_TYPE[self.kind]
        self.digests, self.payload_len, self.frames = [], [], []
        for n in sizes.tolist():
            payload = rng.bytes(n)
            self.digests.append(ref.digest(payload))
            self.payload_len.append(n)
            self.frames.append(ref.frame_header(n, type_id) + payload)

    def __len__(self) -> int:
        return len(self.frames)


class ClientTraffic:
    """All one client sends: its pool, cycled by one endless session."""

    def __init__(self, params: dict, seed: int, client: int):
        if params.get("session_items"):
            raise ValueError("session_items must be null: one session "
                             "streams for the whole window")
        self.params = params
        self.pool = Pool(params, seed, client)

    def next_session(self, stop_at: float):
        return closed_loop.EndlessPlan(
            self.pool, self.params.get("session_multiple", 1), stop_at)

    def lone_session(self, count: int):
        """A warm-up session of `count` files from the pool's start: the
        files the endless session sends first, in its order."""
        return closed_loop.FinitePlan(self.pool, 0, count).cursor()
