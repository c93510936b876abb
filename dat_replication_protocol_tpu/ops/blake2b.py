"""Batched BLAKE2b on device (JAX/XLA, TPU-first).

The reference does no hashing at all; content-addressing lives above it in
dat core.  The TPU-native framework pulls it into the data plane
(BASELINE.json north star: "batched BLAKE2b ... thousands of blobs per XLA
dispatch").  Design:

* 64-bit words are (hi, lo) uint32 lane pairs (:mod:`.u64`) — byte-exact
  RFC 7693 BLAKE2b without 64-bit integer lanes.
* The batch dim is the vector dim, in SoA layout: the 16 working-vector
  lanes are 16 separate (hi, lo) pairs of ``(B,)`` vectors, selected by
  Python indexing.  Every 64-bit op is a full-width elementwise VPU op
  over all B items; there are no gathers or dynamic-update-slices in the
  round function.  The 12 rounds are Python-unrolled (static) so XLA sees
  one straight fused elementwise pipeline per block.
* Variable lengths inside one padded batch: a `lax.scan` over the padded
  block axis with per-item ``active`` / ``final`` masks and byte counters —
  no data-dependent shapes, no recompiles across batches of the same padded
  shape.
* Host edge: :func:`blake2b_batch` packs ``list[bytes]`` into padded uint32
  arrays (bucketed by power-of-two block count to bound padding waste and
  compile count) and unpacks digests, preserving submit order — the
  completion-queue contract the session backend relies on
  (reference semantics: decode.js:87-99 pending accounting).

Per-item payloads are limited to < 2 GiB (byte counters carried in uint32;
larger streams go through the Rabin chunker first, mirroring the
reference's "blobs are streamed, never materialized" discipline,
reference: README.md:73).
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.device import BUCKETS as _BUCKETS
from ..obs.device import jit_site as _jit_site
from ..obs.device import note_engine as _note_engine
from ..obs.metrics import OBS as _OBS
from ..obs.metrics import counter as _counter
from ..utils.payload import PayloadParts
from ..utils.trace import span
from .u64 import U32, add64, add64_3, ror64

# device-transfer attribution (OBSERVABILITY.md device-telemetry
# catalog): message words staged host->device per batch dispatch, and
# digest bytes fetched device->host at collect
_M_H2D = _counter("device.h2d.bytes")
_M_D2H = _counter("device.d2h.bytes")

DIGEST_SIZE = 32  # BLAKE2b-256 default, dat's content-hash size
BLOCK_BYTES = 128

_IV = (
    0x6A09E667F3BCC908,
    0xBB67AE8584CAA73B,
    0x3C6EF372FE94F82B,
    0xA54FF53A5F1D36F1,
    0x510E527FADE682D1,
    0x9B05688C2B3E6C1F,
    0x1F83D9ABFB41BD6B,
    0x5BE0CD19137E2179,
)
_IV_HI = np.array([w >> 32 for w in _IV], dtype=np.uint32)
_IV_LO = np.array([w & 0xFFFFFFFF for w in _IV], dtype=np.uint32)

_SIGMA = np.array(
    [
        [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15],
        [14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3],
        [11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4],
        [7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8],
        [9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13],
        [2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9],
        [12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11],
        [13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10],
        [6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5],
        [10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0],
    ],
    dtype=np.int32,
)
# rounds 10, 11 reuse schedules 0, 1
_ROUND_SIGMA = [_SIGMA[r % 10] for r in range(12)]

# the 8 G applications per round: (a, b, c, d) working-vector lane indices,
# columns then diagonals (RFC 7693 §3.2)
_G_LANES = (
    (0, 4, 8, 12),
    (1, 5, 9, 13),
    (2, 6, 10, 14),
    (3, 7, 11, 15),
    (0, 5, 10, 15),
    (1, 6, 11, 12),
    (2, 7, 8, 13),
    (3, 4, 9, 14),
)


def _g(v, a, b, c, d, x, y):
    """One G mix on SoA state: ``v`` is a list of 16 (hi, lo) pairs of (B,)
    vectors; lane selection is Python indexing, so the whole mix lowers to
    full-width elementwise VPU ops — no gathers, no dynamic-update-slices.
    (The earlier (B, 16) array-of-struct layout spent its time in per-lane
    scatter updates and 16-wide minor-dim padding; SoA is ~3 orders of
    magnitude faster on the VPU.)
    """
    (ah, al), (bh, bl), (ch, cl), (dh, dl) = v[a], v[b], v[c], v[d]
    xh, xl = x
    yh, yl = y

    ah, al = add64_3(ah, al, bh, bl, xh, xl)
    dh, dl = ror64(dh ^ ah, dl ^ al, 32)
    ch, cl = add64(ch, cl, dh, dl)
    bh, bl = ror64(bh ^ ch, bl ^ cl, 24)
    ah, al = add64_3(ah, al, bh, bl, yh, yl)
    dh, dl = ror64(dh ^ ah, dl ^ al, 16)
    ch, cl = add64(ch, cl, dh, dl)
    bh, bl = ror64(bh ^ ch, bl ^ cl, 63)

    v[a], v[b], v[c], v[d] = (ah, al), (bh, bl), (ch, cl), (dh, dl)


def _rounds_unrolled(v, m):
    """All 12 rounds Python-unrolled: one straight ~5k-op elementwise DAG.

    Best runtime on TPU (XLA fuses the whole chain, zero loop or gather
    overhead) but pathological to *compile* on the CPU backend's LLVM
    pipeline — hence the scanned variant below for host runs.
    """
    for sigma in _ROUND_SIGMA:
        for gi, (a, b, c, d) in enumerate(_G_LANES):
            _g(v, a, b, c, d, m[sigma[2 * gi]], m[sigma[2 * gi + 1]])
    return v


def _rounds_scanned(v, m, sigma=None):
    """The 12 rounds as a lax.scan with runtime sigma gathers.

    ~12x smaller HLO than the unrolled form: the body is one round (8 G
    mixes) and the per-round message schedule is a 16-row gather from the
    stacked message words.  Used on the CPU backend where compile time,
    not VPU throughput, is the binding constraint (tests, virtual-mesh
    dry runs).  ``sigma`` overrides the (12, 16) schedule table — pallas
    kernels must pass it in as an input (no closure constants allowed).
    """
    vh = jnp.stack([p[0] for p in v])
    vl = jnp.stack([p[1] for p in v])
    mh = jnp.stack([p[0] for p in m])
    ml = jnp.stack([p[1] for p in m])
    sig = jnp.asarray(np.stack(_ROUND_SIGMA)) if sigma is None else sigma

    def round_body(carry, sig_r):
        vh, vl = carry
        xh = jnp.take(mh, sig_r, axis=0)
        xl = jnp.take(ml, sig_r, axis=0)
        vv = [(vh[i], vl[i]) for i in range(16)]
        for gi, (a, b, c, d) in enumerate(_G_LANES):
            _g(vv, a, b, c, d, (xh[2 * gi], xl[2 * gi]), (xh[2 * gi + 1], xl[2 * gi + 1]))
        return (
            jnp.stack([p[0] for p in vv]),
            jnp.stack([p[1] for p in vv]),
        ), None

    (vh, vl), _ = jax.lax.scan(round_body, (vh, vl), sig)
    return [(vh[i], vl[i]) for i in range(16)]


def compress_soa(h, m, t_lo, is_final, unroll: bool | None = None, sigma=None,
                 t_hi=None):
    """One BLAKE2b compression in SoA layout.

    ``h``: list of 8 (hi, lo) pairs of (B,) uint32 vectors; ``m``: list of
    16 such pairs (message words); ``t_lo``: (B,) uint32 byte counter after
    this block; ``t_hi``: optional (B,) high counter word for streams past
    4 GiB (None = zero, the single-dispatch case); ``is_final``: (B,) bool
    last-block flags.  Returns the new h.

    ``unroll=None`` picks per backend: unrolled rounds on accelerators,
    scanned rounds on CPU (see the two round helpers).  Both are
    byte-exact RFC 7693.
    """
    if unroll is None:
        unroll = jax.default_backend() != "cpu"
    shape = t_lo.shape  # any batch shape: (B,) under scan, (8, B/8) in pallas
    v = [None] * 16
    for i in range(8):
        v[i] = h[i]
        v[8 + i] = (
            jnp.full(shape, _IV_HI[i], U32),
            jnp.full(shape, _IV_LO[i], U32),
        )
    v12_hi = v[12][0] if t_hi is None else v[12][0] ^ t_hi
    v[12] = (v12_hi, v[12][1] ^ t_lo)
    f = jnp.where(is_final, U32(0xFFFFFFFF), U32(0))
    v[14] = (v[14][0] ^ f, v[14][1] ^ f)

    if unroll:
        v = _rounds_unrolled(v, m)
    else:
        v = _rounds_scanned(v, m, sigma)

    return [
        (hh ^ v[i][0] ^ v[i + 8][0], hl ^ v[i][1] ^ v[i + 8][1])
        for i, (hh, hl) in enumerate(h)
    ]


def compress(hh, hl, mh, ml, t_lo, is_final, unroll: bool | None = None):
    """Array-of-struct wrapper over :func:`compress_soa`.

    state (B, 8) hi/lo pairs, block (B, 16) pairs — the layout the packers
    and the Merkle level op exchange.  Unpacking to SoA costs 24 strided
    slices + 2 stacks per block, negligible against the ~4k elementwise ops
    of the 12 rounds.
    """
    h = [(hh[:, i], hl[:, i]) for i in range(8)]
    m = [(mh[:, i], ml[:, i]) for i in range(16)]
    h = compress_soa(h, m, t_lo, is_final, unroll=unroll)
    return (
        jnp.stack([p[0] for p in h], axis=1),
        jnp.stack([p[1] for p in h], axis=1),
    )


def initial_state(batch: int, digest_size: int = DIGEST_SIZE):
    """h0 = IV ^ parameter block (sequential mode, no key)."""
    hh = jnp.broadcast_to(jnp.asarray(_IV_HI), (batch, 8))
    hl = jnp.broadcast_to(jnp.asarray(_IV_LO), (batch, 8))
    param_lo = U32(0x01010000 ^ digest_size)  # digest | key<<8 | fanout | depth
    hl = hl.at[:, 0].set(hl[:, 0] ^ param_lo)
    return hh, hl


def _blake2b_packed_impl(mh, ml, lengths, digest_size: int = DIGEST_SIZE):
    """Hash a padded batch: mh/ml (B, nblocks, 16) uint32, lengths (B,).

    Padding bytes in the final partial block MUST be zero (the host packer
    guarantees this).  Returns digest words as (hh, hl), each (B, 8).
    """
    B, nblocks, _ = mh.shape
    hh, hl = initial_state(B, digest_size)
    lengths = lengths.astype(U32)
    # ceil(len/128), minimum 1: an empty message still compresses one block
    item_blocks = jnp.maximum((lengths + U32(127)) >> U32(7), U32(1))

    # carry in SoA layout — 16 flat (B,) vectors — so the scan body is a
    # pure elementwise DAG with no per-block stack/unstack
    carry0 = tuple(hh[:, i] for i in range(8)) + tuple(hl[:, i] for i in range(8))

    # message words to (nblocks, 16, B): each word a contiguous (B,) row in
    # the lane dim (the (B, 16) minor-dim layout pads 16 -> 128 lanes and
    # turns every per-word slice into a strided read)
    mh = jnp.transpose(mh, (1, 2, 0))
    ml = jnp.transpose(ml, (1, 2, 0))

    def step(carry, xs):
        h = [(carry[i], carry[i + 8]) for i in range(8)]
        bmh, bml, k = xs
        m = [(bmh[i], bml[i]) for i in range(16)]
        active = k < item_blocks
        final = k == item_blocks - U32(1)
        t_lo = jnp.minimum(lengths, (k + U32(1)) << U32(7))
        nh = compress_soa(h, m, t_lo, final)
        out = tuple(
            jnp.where(active, nh[i][0], h[i][0]) for i in range(8)
        ) + tuple(jnp.where(active, nh[i][1], h[i][1]) for i in range(8))
        return out, None

    ks = jnp.arange(nblocks, dtype=jnp.uint32)
    carry, _ = jax.lax.scan(step, carry0, (mh, ml, ks))
    return jnp.stack(carry[:8], axis=1), jnp.stack(carry[8:], axis=1)


blake2b_packed = functools.partial(jax.jit, static_argnames=("digest_size",))(
    _blake2b_packed_impl
)
# donated twin: the staged mh/ml message buffers are throwaway (packed
# on the host, consumed by exactly one dispatch), so donating them lets
# the allocator hand their HBM straight to the NEXT batch's staging —
# the "two donated input buffers" of the double-buffered upload path
# (ISSUE 7): dispatch N+1's h2d streams into memory dispatch N just
# released instead of growing the live set.  CPU jax ignores donation
# (and warns), so callers route here only when the backend honors it.
blake2b_packed_donated = functools.partial(
    jax.jit, static_argnames=("digest_size",), donate_argnums=(0, 1)
)(_blake2b_packed_impl)

# recompile sentinel (obs.device): jit specializes per (B, nblocks) —
# this is THE site the power-of-two bucketing below exists to protect
blake2b_packed = _jit_site("ops.blake2b.packed", blake2b_packed)
blake2b_packed_donated = _jit_site("ops.blake2b.packed_donated",
                                   blake2b_packed_donated)


def split_words(words):
    """Raw little-endian u32 message words ``(B, nblocks*32)`` (a row is
    the item's zero-padded bytes viewed ``<u4``) -> ``(mh, ml)``, each
    ``(B, nblocks, 16)``: u32 index 2k is 64-bit word k's low half,
    2k+1 its high half.  Traced inside the one program per bucket, so
    the de-interleave is device time, not a host pass — the scan's
    first step (the Pallas kernel reads the rows unsplit).  Two stride-2
    slices of the 2-D rows, not ``reshape(B, nb, 16, 2)[..., h]``: by
    the v5e compiler's own cycle estimates the minor-dim-of-2 form costs
    three times the copy work (PERF.md §6)."""
    B = words.shape[0]
    return (words[:, 1::2].reshape(B, -1, 16),
            words[:, 0::2].reshape(B, -1, 16))


def _blake2b_words_impl(words, lengths, digest_size: int = DIGEST_SIZE):
    """:func:`blake2b_packed` over the raw words :func:`stage_payloads`
    lays out: the hi/lo split happens here, on the device."""
    mh, ml = split_words(words)
    return _blake2b_packed_impl(mh, ml, lengths, digest_size)


blake2b_words = _jit_site(
    "ops.blake2b.words",
    functools.partial(jax.jit, static_argnames=("digest_size",))(
        _blake2b_words_impl),
)
# donated twin: the staged words are consumed by exactly one dispatch
blake2b_words_donated = _jit_site(
    "ops.blake2b.words_donated",
    functools.partial(jax.jit, static_argnames=("digest_size",),
                      donate_argnums=(0,))(_blake2b_words_impl),
)


def donation_supported() -> bool:
    """Whether this backend honors buffer donation: the ONE owner of the
    donated-vs-plain dispatch decision (CPU jax silently ignores
    donation and logs a warning per call).  ``DAT_DONATE=1/0``
    overrides, for tests and experiments."""
    import os

    force = os.environ.get("DAT_DONATE")
    if force == "1":
        return True
    if force == "0":
        return False
    return jax.default_backend() in ("tpu", "gpu")


@jax.jit
def blake2b_update(hh, hl, t_hi, t_lo, mh, ml, seg_lengths, is_last):
    """Advance chaining states over one packed segment per item.

    The resumable core of streaming hashing: a message is split into
    segments dispatched one at a time, so a blob of any size is hashed in
    bounded device memory — the device-scale analogue of the reference's
    "blobs are streamed, never materialized" (reference: README.md:73).

    ``hh``/``hl``: (B, 8) chaining state; ``t_hi``/``t_lo``: (B,) uint32
    pair = bytes already compressed (a multiple of 128 per RFC 7693
    block chaining); ``mh``/``ml``: (B, nblocks, 16) packed segment
    words; ``seg_lengths``: (B,) bytes in this segment — non-final
    segments must be full-block multiples; ``is_last``: (B,) bool.

    Returns ``(hh, hl, t_hi, t_lo)`` advanced past the segment.  The
    empty-message case (zero-length last segment with zero counter)
    compresses the mandatory single zero block.
    """
    B, nblocks, _ = mh.shape
    seg_lengths = seg_lengths.astype(U32)
    is_last = is_last.astype(bool)
    raw_blocks = (seg_lengths + U32(127)) >> U32(7)
    t_zero = (t_hi == U32(0)) & (t_lo == U32(0))
    item_blocks = jnp.where(
        is_last & (raw_blocks == U32(0)) & t_zero, U32(1), raw_blocks
    )

    carry0 = tuple(hh[:, i] for i in range(8)) + tuple(hl[:, i] for i in range(8))
    mh_t = jnp.transpose(mh, (1, 2, 0))
    ml_t = jnp.transpose(ml, (1, 2, 0))

    def step(carry, xs):
        h = [(carry[i], carry[i + 8]) for i in range(8)]
        bmh, bml, k = xs
        m = [(bmh[i], bml[i]) for i in range(16)]
        active = k < item_blocks
        final = is_last & (k == item_blocks - U32(1))
        inc = jnp.minimum(seg_lengths, (k + U32(1)) << U32(7))
        bt_hi, bt_lo = add64(t_hi, t_lo, jnp.zeros_like(inc), inc)
        nh = compress_soa(h, m, bt_lo, final, t_hi=bt_hi)
        out = tuple(
            jnp.where(active, nh[i][0], h[i][0]) for i in range(8)
        ) + tuple(jnp.where(active, nh[i][1], h[i][1]) for i in range(8))
        return out, None

    ks = jnp.arange(nblocks, dtype=jnp.uint32)
    carry, _ = jax.lax.scan(step, carry0, (mh_t, ml_t, ks))
    nt_hi, nt_lo = add64(t_hi, t_lo, jnp.zeros_like(seg_lengths), seg_lengths)
    return (
        jnp.stack(carry[:8], axis=1),
        jnp.stack(carry[8:], axis=1),
        nt_hi,
        nt_lo,
    )


blake2b_update = _jit_site("ops.blake2b.update", blake2b_update)


class Blake2bStream:
    """Incremental BLAKE2b over bounded device dispatches (one stream).

    ``update(bytes)`` buffers until a full segment is available, then
    advances the on-device (h, t) chaining state via
    :func:`blake2b_update`; ``digest()`` flushes the tail.  Peak host
    memory is O(segment_bytes) regardless of stream length, and the
    64-bit byte counter supports streams past 4 GiB — this removes the
    session backend's whole-blob host buffering and the < 2 GiB item cap.

    Middle segments all share one padded shape (one XLA compile); the
    final partial segment is bucketed to a power-of-two block count.
    """

    def __init__(self, digest_size: int = DIGEST_SIZE,
                 segment_bytes: int = 1 << 22, max_inflight: int = 2):
        if segment_bytes % BLOCK_BYTES:
            raise ValueError(f"segment_bytes must be a multiple of {BLOCK_BYTES}")
        self._digest_size = digest_size
        self._seg = segment_bytes
        self._max_inflight = max(1, max_inflight)
        self._fences: list = []  # oldest-first in-flight segment counters
        hh, hl = initial_state(1, digest_size)
        z = jnp.zeros((1,), U32)
        self._state = (hh, hl, z, z)
        self._pending = bytearray()
        self._digest: bytes | None = None
        self.length = 0

    def update(self, data) -> "Blake2bStream":
        if self._digest is not None:
            raise RuntimeError("update() after digest()")
        self._pending += bytes(data)
        self.length += len(data)
        # strictly '>' — the final block must go out WITH the final flag,
        # so when pending lands exactly on a segment boundary it is held
        # for digest() (an empty non-final segment can't set the flag)
        while len(self._pending) > self._seg:
            seg = bytes(self._pending[: self._seg])
            del self._pending[: self._seg]
            self._advance(seg, last=False)
        return self

    def _advance(self, seg: bytes, last: bool) -> None:
        import jax

        hh, hl, thi, tlo = self._state
        nblocks = max(1, -(-len(seg) // BLOCK_BYTES))
        if last:
            nblocks = _bucket_nblocks(nblocks)  # bound tail-shape compiles
        mh, ml, lengths = pack_payloads([seg], nblocks=nblocks)
        # stage the upload explicitly: device_put returns immediately and
        # the transfer streams while the device is still compressing the
        # previous segments — H2D rides under compute instead of after it
        mh_d = jax.device_put(mh)
        ml_d = jax.device_put(ml)
        self._state = blake2b_update(
            hh, hl, thi, tlo,
            mh_d, ml_d, jnp.asarray(lengths),
            jnp.asarray([last]),
        )
        # bounded async dispatch: without a periodic barrier the host can
        # outrun the device and queue every segment's message arrays in
        # RAM — the O(chunk) discipline would silently become O(blob).
        # Fetching a (tiny) counter word is the completion barrier.  The
        # fence targets the OLDEST in-flight segment, not the newest:
        # waiting on the newest would drain the whole pipeline and stall
        # the next segment's upload behind it (round-3 verdict weak #5).
        self._fences.append(self._state[3])
        while len(self._fences) >= self._max_inflight:
            np.asarray(self._fences.pop(0))

    def digest(self) -> bytes:
        if self._digest is None:
            self._advance(bytes(self._pending), last=True)
            self._pending.clear()
            hh, hl, _, _ = self._state
            self._digest = digests_to_bytes(hh, hl, self._digest_size)[0]
        return self._digest


# ---------------------------------------------------------------------------
# host edge: bytes <-> padded uint32 batches
# ---------------------------------------------------------------------------


# slot widths up to this are zeroed by ONE fill of the whole staging
# buffer before the row copies; wider slots zero each row's tail (and the
# batch-padding rows) instead, so a full 1 MiB slot is written once
_FILL_WHOLE_MAX = 8192

# the staging pool's bound: today's largest bucket (1,024 x 1 MiB) twice
_STAGE_POOL_BYTES = 2 << 30

_M_STAGE_REUSE = _counter("digest.stage.reuse")
_M_STAGE_ALLOC = _counter("digest.stage.alloc")


class _StagePool:
    """Process-wide host staging buffers for :func:`blake2b_batch_begin`,
    oldest first, bounded in bytes.

    ``device_put`` returns before the transfer and jax may read the host
    array until it completes, so a buffer is parked with a FENCE — an
    output of the program that consumed it — and handed out again only
    once that fence is observed ready.  ``take`` never waits: nothing
    ready of that shape means a fresh ``np.empty``.  Shared by every
    pipeline of the process (the ``plain`` sidecar runs one per
    connection), hence the lock and the bound."""

    def __init__(self, max_bytes: int):
        self._max_bytes = max_bytes
        self._lock = threading.Lock()
        self._parked: collections.deque = collections.deque()
        self._bytes = 0

    def take(self, shape: tuple[int, int]) -> np.ndarray:
        with self._lock:
            for k, (buf, fence) in enumerate(self._parked):
                if buf.shape == shape and fence.is_ready():
                    del self._parked[k]
                    self._bytes -= buf.nbytes
                    break
            else:
                buf = None
        if _OBS.on:
            (_M_STAGE_ALLOC if buf is None else _M_STAGE_REUSE).inc()
        return np.empty(shape, dtype=np.uint8) if buf is None else buf

    def give(self, buf: np.ndarray, fence) -> None:
        with self._lock:
            self._parked.append((buf, fence))
            self._bytes += buf.nbytes
            while self._bytes > self._max_bytes:
                self._bytes -= self._parked.popleft()[0].nbytes


_STAGE_POOL = _StagePool(_STAGE_POOL_BYTES)


def _lay_parts(flat: memoryview, off: int, payload: PayloadParts) -> None:
    for part in payload.parts:
        end = off + len(part)
        flat[off:end] = part
        off = end


def dealt_rows(n_items: int, per: int, shards: int) -> np.ndarray:
    """The row of each item when a bucket's items are dealt round
    ``shards`` equal shards of ``per`` rows: item ``i`` goes to shard
    ``i % shards``, its slot ``i // shards`` — so every chip of a mesh
    holds payload from the first few items on, not the first chip all
    of a small batch."""
    i = np.arange(n_items)
    return (i % shards) * per + i // shards


def stage_payloads(payloads, buf: np.ndarray, shards: int = 1) -> np.ndarray:
    """Lay ``payloads`` into the rows of ``buf`` ((rows, nblocks*128)
    uint8, contents arbitrary): one ``memcpy`` per payload — per piece
    of one held as its pieces (:class:`..utils.payload.PayloadParts`:
    the row is where they are joined) — and every byte past it zeroed:
    the row's tail and the rows beyond ``len(payloads)`` (batch
    padding), which is the zero-padding contract of
    :func:`blake2b_packed`.  Returns the ``(rows,)`` uint32 lengths.
    Item ``i`` lies in row ``i``; with ``shards`` > 1 (the rows divided
    over a mesh's chips) in row :func:`dealt_rows` gives it, the same
    copies and as many bytes zeroed.

    THE routine that puts payload bytes into staging rows:
    :func:`blake2b_batch_begin` ships ``buf`` viewed ``<u4`` as it is
    and the kernel reads the rows where they lie (the scan splits them
    first, :func:`split_words`); :func:`pack_payloads` splits the same
    rows in numpy.
    """
    rows, width = buf.shape
    n_items = len(payloads)
    lens = np.fromiter(map(len, payloads), dtype=np.int64, count=n_items)
    longest = int(lens.max(initial=0))
    if longest >= 1 << 31:
        raise ValueError("per-item payload limit is < 2 GiB; chunk first")
    if longest > width:
        raise ValueError(
            f"nblocks={width // BLOCK_BYTES} < required "
            f"{-(-longest // BLOCK_BYTES)}")
    lengths = np.zeros((rows,), dtype=np.uint32)
    flat = memoryview(buf.reshape(-1))
    if shards > 1:
        _lay_dealt(payloads, lens, buf, flat, lengths, shards)
        return lengths
    lengths[:n_items] = lens
    off = 0
    if width <= _FILL_WHOLE_MAX:
        buf.fill(0)
        for p in payloads:
            if type(p) is PayloadParts:
                _lay_parts(flat, off, p)
            else:
                flat[off:off + len(p)] = p
            off += width
    else:
        for i, p in enumerate(payloads):
            n = len(p)
            if type(p) is PayloadParts:
                _lay_parts(flat, off, p)
            else:
                flat[off:off + n] = p
            if n < width:
                buf[i, n:] = 0
            off += width
        buf[n_items:] = 0
    return lengths


def _lay_dealt(payloads, lens, buf, flat, lengths, shards: int) -> None:
    """:func:`stage_payloads` for rows divided into ``shards``.  The
    padding rows are four blocks, one a shard, and are zeroed by ONE
    numpy fill, BEFORE the copies: a large fill releases the interpreter
    lock, and taking it back from a busy thread (the edge loop) costs up
    to a switch interval each time — four fills read 32 ms against 17
    for one beside a busy thread (PERF.md section 6, PR 35)."""
    rows, width = buf.shape
    per = rows // shards
    n_items = len(payloads)
    at = dealt_rows(n_items, per, shards)
    lengths[at] = lens
    whole = width <= _FILL_WHOLE_MAX
    if whole:
        buf.fill(0)
    else:
        # every shard's rows from the last slot that not every shard
        # fills: the items dealt that slot are laid over the zeros
        full_slots = n_items // shards
        buf.reshape(shards, per, width)[:, full_slots:, :] = 0
    for r, p in zip(at.tolist(), payloads):
        off = r * width
        n = len(p)
        if type(p) is PayloadParts:
            _lay_parts(flat, off, p)
        else:
            flat[off:off + n] = p
        if n < width and not whole:
            buf[r, n:] = 0


def pack_payloads(payloads, nblocks: int | None = None):
    """Pack byte strings into padded (B, nblocks, 16) hi/lo uint32 arrays.

    Little-endian 64-bit message words: u32-word index 2k is word k's low
    half, 2k+1 its high half.  Zero padding satisfies the blake2b_packed
    contract.  Rows are laid by :func:`stage_payloads` and split here, on
    the host, for the callers that feed ``(mh, ml, lengths)`` programs;
    the served batch path (:func:`blake2b_batch_begin`) ships the unsplit
    rows and splits on the device.
    """
    B = len(payloads)
    if nblocks is None:
        longest = max(map(len, payloads), default=0)
        nblocks = max(1, -(-longest // BLOCK_BYTES))
    buf = np.empty((B, nblocks * BLOCK_BYTES), dtype=np.uint8)
    lengths = stage_payloads(payloads, buf)
    words = buf.view("<u4").reshape(B, nblocks, 16, 2)
    return words[..., 1].copy(), words[..., 0].copy(), lengths


def digests_to_bytes(hh, hl, digest_size: int = DIGEST_SIZE) -> list[bytes]:
    """Interleave (hi, lo) word pairs back into little-endian digest bytes."""
    hh = np.asarray(hh, dtype=np.uint32)
    hl = np.asarray(hl, dtype=np.uint32)
    B = hh.shape[0]
    out = np.empty((B, 16), dtype=np.uint32)
    out[:, 0::2] = hl
    out[:, 1::2] = hh
    raw = out.astype("<u4").view(np.uint8).reshape(B, 64)
    return [raw[i, :digest_size].tobytes() for i in range(B)]


def _bucket_nblocks(n: int) -> int:
    """Round a block count up to a power of two to bound compile count."""
    from ..utils.num import next_pow2

    return next_pow2(n)


# The batch edge's DECLARED row counts.  ``jit`` specialises a program
# per ``(rows, nblocks)``, and a Pallas program costs seconds to trace and
# lower even when the compile cache holds it, so the staging buffer's row
# count comes from a small set that depends only on the slot's bytes
# (``nblocks * 128``), never on the item count: its least member is the
# largest power of two up to a full kernel tile whose staging stays within
# ``_ROWS_FLOOR_BYTES``, but not under the kernel's smallest tile; the
# others double up to the full tile.  A slot up to 4 KiB has ONE row count
# (a change feed is one program whatever its sessions' sizes); a 1 MiB
# slot has 32, 64 ... 1,024, so a lone blob stages 32 MiB, not a GiB.
_ROWS_FULL = 1024  # one full kernel tile, and the pipelines' item cap
MIN_TILE_ITEMS = 32  # the Pallas kernel's smallest (packed) row tile
_ROWS_FLOOR_BYTES = 4 << 20


def declared_rows(nblocks: int) -> tuple[int, ...]:
    """The row counts a bucket of ``nblocks``-block slots is staged at."""
    least = _ROWS_FULL
    while (least > MIN_TILE_ITEMS
           and least * nblocks * BLOCK_BYTES > _ROWS_FLOOR_BYTES):
        least >>= 1
    return tuple(least << k
                 for k in range((_ROWS_FULL // least).bit_length()))


def batch_rows(n_items: int, nblocks: int) -> int:
    """The smallest declared row count that holds ``n_items``; past a
    full tile (no pipeline submits that: the served paths cap a batch at
    1,024 items) the next power of two, as whole tiles.  No bucket stages
    more than ``max(4 MiB, 32 x slot, 4 x its payload bytes)``: a bucket's
    items each fill over half their slot."""
    return max(declared_rows(nblocks)[0], _bucket_nblocks(n_items))


def shard_rows(n_items: int, nblocks: int, n_devices: int) -> int:
    """A chip's rows when a bucket is laid over ``n_devices`` chips:
    the declared policy applied per chip — the smallest declared row
    count that holds the most items any chip is dealt
    (:func:`dealt_rows`: ``ceil(n_items / n_devices)``).  A shard is
    never under the kernel's smallest tile, and a bucket of a given
    slot width meets the same handful of shapes whatever its item
    count."""
    return batch_rows(-(-n_items // n_devices), nblocks)


@functools.lru_cache(maxsize=None)
def _sharded_words_program(mesh, use_pallas: bool, donate: bool,
                           digest_size: int):
    """The bucket's one program laid over ``mesh`` (1-D): every chip
    runs the single-device words program — the Pallas kernel on a TPU,
    the scan elsewhere — on its shard of the rows, inputs and outputs
    sharded over the batch axis, no collective.  Cached per mesh so that
    a bucket shape traces once; its name, not ``jit_blake2b*``, is what
    tells a sharded run from a one-chip run in a device trace."""
    from jax.sharding import PartitionSpec as P

    if use_pallas:
        from .blake2b_pallas import blake2b_words_pallas as body
    else:
        body = blake2b_words
    rows = P(mesh.axis_names[0])

    def mesh_blake2b_words(words, lengths):
        return jax.shard_map(
            lambda w, n: body(w, n, digest_size), mesh=mesh,
            in_specs=(rows, rows), out_specs=(rows, rows),
            check_vma=False)(words, lengths)

    return _jit_site(
        "ops.blake2b.mesh_words",
        jax.jit(mesh_blake2b_words, donate_argnums=(0,) if donate else ()))


def blake2b_batch_begin(
    payloads, digest_size: int = DIGEST_SIZE, use_pallas: bool | None = None,
    mesh=None,
):
    """Dispatch batched hashing; return a zero-arg ``collect()`` closure.

    JAX dispatch is asynchronous: the device starts compressing as soon
    as this returns, while the host goes back to parsing.  ``collect()``
    blocks on the transfers and yields digests in submit order — the
    split the async DigestPipeline uses to overlap parse and hash.
    Beside it ride ``collect.start_d2h()`` (begin the readback, no
    wait) and ``collect.ready()`` (no wait: are the digests computed).

    Items are grouped into power-of-two block-count buckets; each bucket
    is one padded dispatch at a declared row count (:func:`batch_rows`),
    so the programs a process builds are one per slot width for small
    slots and a handful for wide ones, whatever the item counts.
    ``use_pallas=None`` is the Pallas kernel on a TPU backend and the
    portable XLA scan elsewhere, for every bucket alike: on a chip no
    item count reaches the scan.

    A bucket is staged as ONE array of raw little-endian u32 message
    words (:func:`stage_payloads`: one copy per item, no host-side
    de-interleave) and ONE ``device_put`` of the words and their
    lengths; the bucket's one program reads the rows as they were
    shipped — the Pallas kernel turns them onto lanes in VMEM, the scan
    splits them first (:func:`split_words`) — and its jit call is the
    launch's only jax call.  Staging buffers come from a small
    process-wide pool (:class:`_StagePool`).  A bucket's digests come
    back whole and the items' rows are picked on the host, so no
    program is built per item count.

    ``mesh`` (a 1-D :class:`jax.sharding.Mesh`; None: one device) lays
    every bucket over the mesh's chips — the same staging, the same
    spans and bucket table, one engine in two layouts: the row count is
    ``n`` chips x a per-chip declared count (:func:`shard_rows`), items
    are dealt round the chips (:func:`dealt_rows`: every chip hashes
    payload, whatever the batch), the one ``device_put`` carries a
    batch-dim ``NamedSharding``, and each chip runs the words program on
    its shard with no exchange (:func:`_sharded_words_program`).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    donate = donation_supported()
    # the two layouts differ in three places, gathered here: the rows a
    # bucket is staged at (``n_dev``), where the one ``device_put``
    # sends the rows and their lengths (``by_rows``), and which program
    # it runs; which rows are the items' (``dealt_rows``) follows from
    # the first.  Both fetch a bucket's digests whole and pick the
    # items' rows in collect(): a slice on the device is a program per
    # item count
    if mesh is None:
        n_dev = 1
        over: dict = {}  # what the spans say beside items and nblocks
        by_rows = None   # the default device
        if use_pallas:
            if donate:
                from .blake2b_pallas import (
                    blake2b_words_pallas_donated as words_fn)
            else:
                from .blake2b_pallas import blake2b_words_pallas as words_fn
        else:
            words_fn = blake2b_words_donated if donate else blake2b_words
        program = functools.partial(words_fn, digest_size=digest_size)
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        n_dev = mesh.devices.size
        over = {"devices": n_dev}
        by_rows = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        program = _sharded_words_program(mesh, use_pallas, donate,
                                         digest_size)
    engine = "pallas" if use_pallas else "xla-scan"
    if _OBS.on:
        _note_engine("blake2b.batch", engine, items=len(payloads))
    buckets: dict[int, list[int]] = {}
    for i, p in enumerate(payloads):
        nb = _bucket_nblocks(max(1, -(-len(p) // BLOCK_BYTES)))
        buckets.setdefault(nb, []).append(i)
    handles = []
    for nb, idxs in buckets.items():
        # rows beyond the items are empty payloads: valid, and their
        # digests are dropped in collect()
        per = shard_rows(len(idxs), nb, n_dev)
        Bp = n_dev * per
        at = dealt_rows(len(idxs), per, n_dev)  # one chip: the first rows
        # one copy per item into a (Bp, nb*128) byte buffer, shipped as
        # raw <u4 words, which the program reads as they lie
        with span("digest.pack", items=len(idxs), nblocks=nb, **over):
            buf = _STAGE_POOL.take((Bp, nb * BLOCK_BYTES))
            lengths = stage_payloads([payloads[i] for i in idxs], buf, n_dev)
            words = buf.view("<u4")
        if _OBS.on:
            # with the payload bytes the kernel is handed (a blob hashed
            # on the host stream is not among them)
            _BUCKETS.note(engine, nb, len(idxs), Bp,
                          int(lengths.sum(dtype=np.int64)))
            _M_H2D.inc(words.nbytes + lengths.nbytes)
        # stage explicitly (device_put returns immediately): the upload
        # streams while earlier batches compress, and — when donation is
        # supported — the staged words are DONATED to the dispatch, so
        # successive batches recycle staging HBM instead of growing the
        # live set.  ONE call ships the rows and their lengths (over a
        # mesh, every chip its shard of both).  The span is the HOST's
        # time inside the call, not the link's.
        with span("digest.h2d", items=len(idxs), nblocks=nb, **over):
            words_d, lengths_d = jax.device_put((words, lengths), by_rows)
        with span("digest.launch", items=len(idxs), nblocks=nb, **over):
            # the jit call is the launch's one child
            with span("digest.launch.program"):
                hh, hl = program(words_d, lengths_d)
            # hh ready => the program ran => the transfer out of buf is
            # over
            _STAGE_POOL.give(buf, hh)
            handles.append((idxs, hh, hl, at))

    def start_d2h() -> None:
        # begin the digest readback WITHOUT blocking: the copies queue
        # behind the programs, so by collect() time the words are local
        # or on their way.  Idempotent; the DigestPipeline calls this at
        # the end of the batch's own dispatch.
        for _, hh, hl, _ in handles:
            hh.copy_to_host_async()
            hl.copy_to_host_async()

    def ready() -> bool:
        # non-blocking: has the device produced every bucket's digests
        # (what the staging pool asks of its fences).  True means
        # collect() waits for no program, at most for a readback's tail.
        return all(hh.is_ready() and hl.is_ready()
                   for _, hh, hl, _ in handles)

    n_items = len(payloads)  # the closures below keep no payload alive

    def collect() -> list[bytes]:
        out: list[bytes | None] = [None] * n_items
        for idxs, hh, hl, at in handles:
            if _OBS.on:
                # the items' two 8 x u32 digest halves = 64 B/item (the
                # padding rows' come with them, uncounted)
                _M_D2H.inc(64 * len(idxs))
            # the one place the host legitimately waits for the device
            with span("digest.d2h_wait", items=len(idxs), **over):
                hh = np.asarray(hh)
                hl = np.asarray(hl)
            with span("digest.unpack", items=len(idxs), **over):
                hh, hl = hh[at], hl[at]
                for i, d in zip(idxs, digests_to_bytes(hh, hl, digest_size)):
                    out[i] = d
        return out  # type: ignore[return-value]

    collect.start_d2h = start_d2h  # type: ignore[attr-defined]
    collect.ready = ready  # type: ignore[attr-defined]
    return collect


# what a pipeline observes of an engine (DigestPipeline): this one takes
# a payload as it arrived — bytes, a view, PayloadParts — and the pack
# is where the pieces are joined
blake2b_batch_begin.takes_parts = True  # type: ignore[attr-defined]


def blake2b_batch(
    payloads, digest_size: int = DIGEST_SIZE, use_pallas: bool | None = None
) -> list[bytes]:
    """Hash a list of byte strings on device; digests in submit order."""
    if not payloads:
        return []
    return blake2b_batch_begin(payloads, digest_size, use_pallas)()
