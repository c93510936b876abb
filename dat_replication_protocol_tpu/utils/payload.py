"""A payload held as its pieces — the hand-off type between the decoder
and the staging row."""

from __future__ import annotations

import numpy as np


class PayloadParts:
    """One payload as the pieces it arrived in, in order: ``bytes``
    objects or read-only views of the receive slabs.  ``len()`` is the
    payload's byte total, so a batching layer counts it like ``bytes``;
    whoever consumes it copies (or hashes) piece by piece — the staging
    row is where the pieces are joined
    (:func:`..ops.blake2b.stage_payloads`).  A view pins its whole
    slab: hold one no longer than the pack that copies it."""

    __slots__ = ("parts", "nbytes")

    def __init__(self, parts):
        self.parts = tuple(parts)
        self.nbytes = sum(map(len, self.parts))

    def __len__(self) -> int:
        return self.nbytes


def buffer_address(buf) -> int:
    """Where a buffer's first byte lies in memory: a view's address
    less its slab's is the view's offset in the slab, which a
    ``memoryview`` does not say."""
    return np.frombuffer(buf, np.uint8).__array_interface__["data"][0]
