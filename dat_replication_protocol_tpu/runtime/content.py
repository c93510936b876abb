"""Content addressing: chunk -> hash -> Merkle root, and version deltas.

The reference moves *already content-addressed* data — dat core above it
chunks blobs, hashes chunks, and exchanges only missing pieces; the wire
protocol's ``Change.value``/blob frames carry the results (reference:
README.md:73, messages/schema.proto:6).  This module composes the
framework's device pipeline into that exact workflow as one API:

* :func:`content_address` — CDC cut a byte stream
  (:func:`..ops.rabin.chunk_stream`), BLAKE2b every chunk in batched
  device dispatches (:func:`..batch.feed.hash_extents`), fold the chunk
  digests to a Merkle root (:mod:`..ops.merkle`).
* :func:`delta` — the transfer set between two versions of a blob: chunks
  of ``new`` whose digests ``old`` does not hold.  Because the cuts are
  content-defined, an insertion/deletion reshuffles only the chunks it
  touches — the delta stays O(edit), not O(blob), which is the entire
  point of CDC dedup.

Everything heavy runs on device; the host sees cut offsets, digests, and
the root.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

from ..obs.device import note_engine as _note_engine
from ..obs.metrics import OBS as _OBS
from ..obs.metrics import counter as _counter
from ..obs.tracing import trace_span as _trace_span

_M_D2H = _counter("device.d2h.bytes")
# single-pass route volume (OBSERVABILITY.md single-pass catalog)
_M_FUSED_BYTES = _counter("cdc.fused.bytes")
_M_FUSED_CHUNKS = _counter("cdc.fused.chunks")


def _extents_from_cuts(cuts) -> tuple[np.ndarray, np.ndarray]:
    """Chunk end-offsets -> (offsets, lengths); single owner of the
    exclusive-ends convention."""
    ends = np.asarray(cuts, dtype=np.int64)
    offs = np.concatenate([np.zeros(1, np.int64), ends[:-1]])
    return offs, ends - offs


@dataclasses.dataclass(frozen=True, eq=False)
class ContentSummary:
    """One blob version's content-addressed identity.

    ``cuts``: chunk end-offsets (exclusive, ascending, last == length);
    ``digests``: (nchunks, 32) uint8 BLAKE2b-256 per chunk, in order;
    ``root``: 32-byte Merkle root over the chunk digests (zero-padded to
    a power of two, so equal content always folds to an equal root).

    Equality/hash use the identity triple (length, cuts, root) — the
    dataclass defaults would tuple-compare the ndarray field, which
    raises; the root already commits to every digest.
    """

    length: int
    cuts: list[int]
    digests: np.ndarray
    root: bytes

    def __eq__(self, other) -> bool:
        if not isinstance(other, ContentSummary):
            return NotImplemented
        return (self.length == other.length and self.cuts == other.cuts
                and self.root == other.root)

    def __hash__(self) -> int:
        return hash((self.length, tuple(self.cuts), self.root))

    @property
    def nchunks(self) -> int:
        return len(self.cuts)

    def extents(self) -> tuple[np.ndarray, np.ndarray]:
        """(offsets, lengths) arrays of the chunks."""
        return _extents_from_cuts(self.cuts)


def _as_u8(data) -> np.ndarray:
    return np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)
    ) else np.asarray(data, dtype=np.uint8)


def resolve_cdc_route() -> str:
    """The ONE owner of the host content-addressing route decision.

    ``fused1p`` (the default) is the single-pass native engine: gear
    candidates, greedy cuts, and chunk BLAKE2b in one sweep
    (``dat_cdc_hash``; cuts and digests byte-identical to the two-pass
    route — the fuzz suite pins it).  Setting ``DAT_CDC_ROUTE`` to any
    OTHER recognized value pins the two-pass route with that extraction
    kernel; unrecognized values resolve to the default, mirroring
    :func:`..ops.rabin.effective_route`.
    """
    route = os.environ.get("DAT_CDC_ROUTE")
    if route in ("bitmask", "first", "fused"):
        return "2p"
    return "fused1p"


def content_digests(data, avg_bits: int = 13,
                    min_size: int | None = None,
                    max_size: int | None = None,
                    route: str | None = None):
    """Chunk cuts AND per-chunk BLAKE2b-256 digests for a byte stream —
    the single-pass bytes->digests API (ISSUE 7 tentpole).

    Returns ``(cuts, digests)``: cut end-offsets (list[int], exclusive,
    last == length) and (nchunks, 32) uint8 digests.  ``route``:
    ``None`` resolves via :func:`resolve_cdc_route`; ``"fused1p"``
    forces the single-pass engine (falls back to two-pass when the
    native library is absent or the shape is out of its range);
    ``"2p"`` forces the two-pass route (the A/B incumbent).

    Host routing ("batch or stay home", same decision as
    :func:`..ops.rabin.chunk_stream`): on a CPU-backed jax the native
    engines serve both routes; on an accelerator the device
    single-residency pipeline does (:mod:`..ops.fused_cdc_hash_pallas`).
    """
    from ..ops.rabin import chunk_stream, _clamp_thin_bits
    from ..utils.routing import prefer_host

    buf = _as_u8(data)
    n = int(buf.size)
    if n == 0:
        return [], np.empty((0, 32), np.uint8)
    if min_size is None:
        min_size = 1 << (avg_bits - 2)
    if max_size is None:
        max_size = 1 << (avg_bits + 2)
    if route is None:
        route = resolve_cdc_route()

    host = prefer_host("DAT_DEVICE_CDC")
    if host and route == "fused1p":
        from . import native

        # the SAME thinning policy as every other route (one owner:
        # _clamp_thin_bits), so cuts are identical across all of them
        thin = _clamp_thin_bits(max(min_size, 1).bit_length() - 1, 1 << 17)
        out = native.cdc_hash(buf, avg_bits, -1 if thin is None else thin,
                              min_size, max_size)
        if out is not None:
            cuts_arr, digests = out
            if _OBS.on:
                _M_FUSED_BYTES.inc(n)
                _M_FUSED_CHUNKS.inc(len(cuts_arr))
                _note_engine("cdc.hash", "fused1p-native", bytes=n)
            return cuts_arr.tolist(), digests
        # out of the fused kernel's range (tiny min_size, no native
        # library): the two-pass route serves it byte-identically
    if host:
        from . import native

        cuts = chunk_stream(buf, avg_bits, min_size, max_size)
        offs, lens = _extents_from_cuts(cuts)
        digests = native.hash_many(buf, offs, lens)
        if digests is None:  # no native library: hashlib loop
            import hashlib

            digests = np.empty((len(cuts), 32), np.uint8)
            for i, (o, ln) in enumerate(zip(offs, lens)):
                digests[i] = np.frombuffer(
                    hashlib.blake2b(buf[o:o + ln].tobytes(),
                                    digest_size=32).digest(), np.uint8)
        if _OBS.on:
            _note_engine("cdc.hash", "two-pass-host", bytes=n)
        return list(map(int, cuts)), digests

    # device: the single-residency pipeline (one upload, CDC + hash off
    # the same resident words) for buffers within its per-call cap; the
    # slabbed two-pass composition for anything larger — and for an
    # EXPLICIT route="2p" (the A/B incumbent must stay the two-pass
    # host-repack composition on every backend, or an A/B's
    # label lies about what ran)
    from ..ops.fused_cdc_hash_pallas import RESIDENCY_CAP

    if route != "2p" and n < RESIDENCY_CAP:
        from ..ops.fused_cdc_hash_pallas import content_begin

        cuts, hh, hl = content_begin(buf, avg_bits, min_size, max_size)()
        if _OBS.on:
            _M_D2H.inc(32 * len(cuts))
            _note_engine("cdc.hash", "device-1residency", bytes=n)
        from ..ops.merkle import digest_matrix

        return list(map(int, cuts)), digest_matrix(hh, hl)
    from ..batch.feed import hash_extents

    cuts = chunk_stream(buf, avg_bits, min_size, max_size)
    offs, lens = _extents_from_cuts(cuts)
    if _OBS.on:
        _note_engine("cdc.hash", "device-two-pass", bytes=n)
    return list(map(int, cuts)), hash_extents(buf, offs, lens)


def content_address(data, avg_bits: int = 13,
                    min_size: int | None = None,
                    max_size: int | None = None) -> ContentSummary:
    """Chunk, hash, and root a byte stream.

    ``data``: bytes or uint8 array.  Empty input has zero chunks and the
    all-zero root (the empty-subtree sentinel of
    :func:`..ops.merkle.pad_leaves`).

    Single-pass restructuring (ISSUE 7): blob bytes are read ONCE.  On a
    CPU host the fused native engine computes cuts and digests in one
    sweep (the old host route streamed the data through the gear scan,
    then re-read every byte through an XLA-scan BLAKE2b that measured
    ~0.001 GiB/s); on an accelerator the words are uploaded once and
    both the CDC kernels and the chunk hash read the same resident
    buffer.  The Merkle fold consumes the digest columns either way.
    """
    from ..ops import merkle
    from ..utils.routing import prefer_host

    buf = _as_u8(data)
    if buf.size == 0:
        return ContentSummary(0, [], np.empty((0, 32), np.uint8), b"\0" * 32)
    with _trace_span("device.content.address", bytes=int(buf.size)):
        on_device = not prefer_host("DAT_DEVICE_CDC")
        if on_device:
            from ..ops.fused_cdc_hash_pallas import RESIDENCY_CAP

            on_device = buf.size < RESIDENCY_CAP
        if on_device:
            # device route: digests stay in HBM through the tree fold;
            # the host copy is one interleave off the same device arrays
            from ..ops.fused_cdc_hash_pallas import content_begin

            cuts, hh, hl = content_begin(buf, avg_bits, min_size,
                                         max_size)()
            (root_bytes,) = merkle.digests_from_device(
                *merkle.root(*merkle.pad_leaves(hh, hl))
            )
            n = len(cuts)
            if _OBS.on:
                _M_D2H.inc(32 * n + 32)  # chunk digests + the root
            digests = merkle.digest_matrix(hh, hl)
            return ContentSummary(int(buf.size), list(map(int, cuts)),
                                  digests, root_bytes)
        cuts, digests = content_digests(buf, avg_bits, min_size, max_size)
        # host tree fold (native engine): byte-identical to the device
        # fold, without routing 32 B/chunk through an XLA CPU program
        root_bytes = merkle.root_host(digests)
    return ContentSummary(int(buf.size), cuts, digests, root_bytes)


def delta(old: ContentSummary, new: ContentSummary) -> list[int]:
    """Chunk indices of ``new`` that ``old`` cannot supply.

    The sender ships exactly these chunks (plus the cut table); the
    receiver reassembles everything else from chunks it already holds —
    dat's dedup exchange, here decided by digest set membership.  Equal
    roots short-circuit to an empty delta.
    """
    if old.root == new.root and old.cuts == new.cuts:
        return []
    have = {old.digests[i].tobytes() for i in range(old.nchunks)}
    return [
        i for i in range(new.nchunks)
        if new.digests[i].tobytes() not in have
    ]


def reassemble(new: ContentSummary, old_data,
               old: ContentSummary, sent: dict[int, bytes]) -> bytes:
    """Receiver-side reconstruction: old chunks + the delta -> new bytes.

    ``sent`` maps chunk index -> bytes for every index in
    ``delta(old, new)``.  Raises ``KeyError`` if a needed chunk is
    neither held nor sent, ``ValueError`` if a supplied chunk's digest
    does not match the summary (corruption check — digests are the
    addresses, so verification is free).
    """
    import hashlib

    old_buf = np.frombuffer(old_data, dtype=np.uint8) if isinstance(
        old_data, (bytes, bytearray, memoryview)
    ) else np.asarray(old_data, dtype=np.uint8)
    by_digest: dict[bytes, tuple[int, int]] = {}
    o_offs, o_lens = old.extents()
    for i in range(old.nchunks):
        by_digest[old.digests[i].tobytes()] = (int(o_offs[i]), int(o_lens[i]))
    out = bytearray()
    for i in range(new.nchunks):
        d = new.digests[i].tobytes()
        if i in sent:
            piece = sent[i]
            if hashlib.blake2b(piece, digest_size=32).digest() != d:
                raise ValueError(f"chunk {i} digest mismatch")
        else:
            off, ln = by_digest[d]
            piece = old_buf[off:off + ln].tobytes()
        out += piece
    return bytes(out)
