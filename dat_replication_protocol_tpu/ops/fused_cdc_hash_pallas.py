"""Single-pass content addressing on device: fused CDC extraction with
an on-chip cross-check, and a single-residency chunk-hash pipeline.

The two-pass device route reads blob bytes twice: once through the gear
CDC kernel (device-resident words), then again through a HOST-side
``pack_ragged`` + re-upload for the BLAKE2b batch — the blob crosses the
host/device boundary twice and the host touches every byte in between.
This module collapses that to ONE residency (ISSUE 7 tentpole):

* :func:`gear_window_first_checked` — the ``fused1p`` extraction kernel:
  the window-first gear scan of :mod:`.rabin_pallas` with an INDEPENDENT
  per-window occupancy reduction fused in, and a consistency flag out.
  The two reductions take different paths through the kernel (packed-
  word first-hit tracking vs an or-accumulate occupancy), so a
  miscompiled or raced reduction surfaces as a flag the host REFUSES to
  cut from (``cdc.fused.crosscheck.refused``; the caller falls back to
  the bitmask route, which recomputes from scratch).
* :func:`pack_extents_device` — ragged chunk extents packed into the
  BLAKE2b batch layout BY THE DEVICE, gathering from the already-
  resident word buffer: no host pack, no second upload.  This is the
  same restructuring for the XLA-scan path (the gather + shift pack is
  portable XLA), so the single-pass win lands on CPU-backed jax too.
* :func:`content_begin` — the composed pipeline: candidates (any
  ``DAT_CDC_ROUTE`` kernel) -> O(candidates) D2H -> native greedy ->
  device-side pack -> batched BLAKE2b -> digests, with the blob words
  uploaded exactly once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.device import jit_site as _jit_site
from ..obs.metrics import OBS as _OBS
from ..obs.metrics import counter as _counter
from .rabin import GROUP, PACK, _gear_step, _popcount32
from .u64 import U32

_SUBLANE = 8
_LANE = 128
_SENT_OFF = 1 << 30  # empty-window sentinel (rabin_pallas convention)

# single-residency per-call cap: the device extent pack computes byte
# positions in int32 (jax's default int), and the highest index it forms
# is offs + nblocks*128 (the PADDED chunk width) — so the cap backs off
# int32 range by a 64 MiB margin rather than sitting exactly at 2 GiB,
# where the last chunk's padding indices would wrap negative and slip
# the validity mask (silently corrupting that chunk's digest).
RESIDENCY_CAP = (1 << 31) - (1 << 26)

# fused-route telemetry (OBSERVABILITY.md single-pass catalog; the
# crosscheck-refusal counter lives at its one increment site,
# ops.rabin.candidates_begin)
_M_FUSED_BYTES = _counter("cdc.fused.bytes")
_M_FUSED_CHUNKS = _counter("cdc.fused.chunks")


def _kernel_wfirst_checked(wref, oref, occref, sth_ref, stl_ref, fidx_ref,
                           fval_ref, oany_ref, *, avg_bits: int, ilp: int,
                           gpw: int):
    """Window-first gear scan with an INDEPENDENT occupancy reduction.

    Same gear chain and first-candidate tracking as
    :func:`.rabin_pallas._kernel_wfirst`; additionally every packed
    accumulator word is OR-folded into a per-window occupancy scratch
    that never consults the fidx/fval tracking.  The flush emits both
    the first-candidate offset and the occupancy word — the wrapper's
    invariant ``(occ != 0) == (offset != SENT)`` ties the two reductions
    together, so a defect in either surfaces as a refusable flag rather
    than silently divergent cuts.
    """
    j = pl.program_id(1)
    mask = U32((1 << avg_bits) - 1)
    btl = sth_ref.shape[-1] // ilp
    sent = U32(0xFFFFFFFF)

    @pl.when(j == 0)
    def _init():
        sth_ref[0] = jnp.zeros(sth_ref.shape[1:], U32)
        stl_ref[0] = jnp.zeros(stl_ref.shape[1:], U32)
        fidx_ref[0] = jnp.full(fidx_ref.shape[1:], sent, U32)
        fval_ref[0] = jnp.zeros(fval_ref.shape[1:], U32)
        oany_ref[0] = jnp.zeros(oany_ref.shape[1:], U32)

    def chunk(a, k):
        return a[:, k * btl : (k + 1) * btl]

    hh = [chunk(sth_ref[0], k) for k in range(ilp)]
    hl = [chunk(stl_ref[0], k) for k in range(ilp)]
    fidx = [chunk(fidx_ref[0], k) for k in range(ilp)]
    fval = [chunk(fval_ref[0], k) for k in range(ilp)]
    oany = [chunk(oany_ref[0], k) for k in range(ilp)]
    valid = j > 0  # group 0 is warm-up context: hits there never count
    wphase = jnp.mod(j - 1, gpw).astype(U32)
    vmask = jnp.where(valid, U32(0xFFFFFFFF), U32(0))

    acc = [jnp.zeros_like(hh[0]) for _ in range(ilp)]
    bit = 0
    pword = 0
    for w in range(GROUP // 4):
        word = wref[0, w]
        for s in range(4):
            for k in range(ilp):
                byte = (chunk(word, k) >> U32(8 * s)) & U32(0xFF)
                hh[k], hl[k] = _gear_step(hh[k], hl[k], byte)
                hit = (hh[k] & mask) == U32(0)
                acc[k] = acc[k] | (hit.astype(U32) << U32(bit))
            bit += 1
            if bit == PACK:
                word_idx = wphase * U32(GROUP // PACK) + U32(pword)
                for k in range(ilp):
                    new = (fidx[k] == sent) & (acc[k] != U32(0)) & valid
                    fidx[k] = jnp.where(new, word_idx, fidx[k])
                    fval[k] = jnp.where(new, acc[k], fval[k])
                    # occupancy: a straight OR fold, blind to the
                    # first-hit tracking above
                    oany[k] = oany[k] | (acc[k] & vmask)
                acc = [jnp.zeros_like(hh[0]) for _ in range(ilp)]
                bit = 0
                pword += 1

    sth_ref[0] = jnp.concatenate(hh, axis=-1)
    stl_ref[0] = jnp.concatenate(hl, axis=-1)

    is_flush = valid & (wphase == U32(gpw - 1))

    @pl.when(is_flush)
    def _flush():
        outs = []
        for k in range(ilp):
            lsb = fval[k] & (U32(0) - fval[k])
            bitpos = _popcount32(lsb - U32(1))
            outs.append(jnp.where(
                fidx[k] != sent,
                fidx[k] * U32(PACK) + bitpos,
                U32(_SENT_OFF),
            ))
        oref[0] = jnp.concatenate(outs, axis=-1)
        occref[0] = jnp.concatenate(oany, axis=-1)
        fidx_ref[0] = jnp.full(fidx_ref.shape[1:], sent, U32)
        oany_ref[0] = jnp.zeros(oany_ref.shape[1:], U32)

    @pl.when(jnp.logical_not(is_flush))
    def _keep():
        fidx_ref[0] = jnp.concatenate(fidx, axis=-1)
        fval_ref[0] = jnp.concatenate(fval, axis=-1)
        oany_ref[0] = jnp.concatenate(oany, axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("avg_bits", "thin_bits", "block_tiles", "interpret",
                     "ilp"),
)
def gear_window_first_checked_native(words, avg_bits: int, thin_bits: int,
                                     block_tiles: int = 8192,
                                     interpret: bool = False, ilp: int = 8):
    """``words``: (ng, GROUP/4, 8, T/8) uint32 (group 0 = warm-up) ->
    ``(firsts, occ)``: per-window first-candidate byte offsets and the
    independent per-window occupancy words, each ``(nwin_per_tile, 8,
    T/8)`` uint32."""
    ng, gw, s, tl = words.shape
    if gw != GROUP // 4 or s != _SUBLANE:
        raise ValueError(f"expected (ng, {GROUP // 4}, 8, T/8); got {words.shape}")
    gpw = (1 << thin_bits) // GROUP
    if gpw < 1 or (ng - 1) % gpw:
        raise ValueError(
            f"window of 2**{thin_bits} B needs payload groups {ng - 1} "
            f"divisible by {gpw}"
        )
    btl = block_tiles // _SUBLANE
    if tl % btl:
        raise ValueError(f"T/8={tl} not a multiple of tile width {btl}")
    if btl % ilp or (btl // ilp) % _LANE:
        raise ValueError(
            f"block_tiles/8={btl} must split into {ilp} lane-multiples"
        )
    nwpt = (ng - 1) // gpw
    grid = (tl // btl, ng)
    kernel = functools.partial(_kernel_wfirst_checked, avg_bits=avg_bits,
                               ilp=ilp, gpw=gpw)
    win_spec = pl.BlockSpec(
        (1, _SUBLANE, btl),
        # groups [1 + w*gpw, 1 + (w+1)*gpw) -> window block w; warm-up
        # step j=0 aliases harmlessly onto block 0 (never written)
        lambda i, j: (jnp.maximum((j - 1) // gpw, 0), 0, i),
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, gw, _SUBLANE, btl), lambda i, j: (j, 0, 0, i)),
        ],
        out_specs=[win_spec, win_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nwpt, _SUBLANE, tl), jnp.uint32),
            jax.ShapeDtypeStruct((nwpt, _SUBLANE, tl), jnp.uint32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the name the device trace shows this kernel under
        name="fused_cdc_window_first",
    )(words)


@functools.partial(
    jax.jit,
    static_argnames=("avg_bits", "thin_bits", "block_tiles", "interpret",
                     "ilp"),
)
def gear_window_first_checked(words, avg_bits: int, thin_bits: int,
                              block_tiles: int | None = None,
                              interpret: bool = False,
                              ilp: int | None = None):
    """``fused1p`` extraction: (T, S/4) prefixed tile rows in (group 0 =
    warm-up, per ``rabin._build_rows``), ``(first, viol)`` out —
    ``first`` the stream-ordered per-window first-candidate offsets
    ((T * nwin_per_tile,) int32, ``1 << 30`` = empty) and ``viol`` the
    count of windows whose two on-chip reductions disagree (the host
    refuses the whole extraction when it is nonzero)."""
    from .rabin_pallas import _to_native_layout

    T, _ = words.shape
    native, Tp, ng, block_tiles, ilp = _to_native_layout(
        words, block_tiles, ilp
    )
    firsts, occ = gear_window_first_checked_native(
        native, avg_bits, thin_bits, block_tiles, interpret, ilp
    )
    nwpt = firsts.shape[0]
    out = jnp.transpose(firsts, (1, 2, 0)).reshape(Tp * nwpt)
    occ_flat = jnp.transpose(occ, (1, 2, 0)).reshape(Tp * nwpt)
    first = out[: T * nwpt].astype(jnp.int32)
    occ_flat = occ_flat[: T * nwpt]
    viol = jnp.sum(
        ((occ_flat != 0) != (first != _SENT_OFF)).astype(jnp.int32)
    )
    return first, viol


gear_window_first_checked = _jit_site(
    "ops.fused_cdc_hash.window_first_checked", gear_window_first_checked
)


# ---------------------------------------------------------------------------
# device-side extent packing: the second blob read stays on device
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("nblocks", "chunk_b"))
def _pack_extents_kernel(words, offs, lens, nblocks: int, chunk_b: int):
    """Gather-pack ``chunk_b`` extents of the device-resident word
    buffer into the (B, nblocks, 16) hi/lo BLAKE2b batch layout.

    Byte i of the stream is ``(words[i >> 2] >> (8 * (i & 3))) & 0xFF``;
    the gather runs over word indices (one u32 fetch per output byte's
    word, fused by XLA), masked past each extent's length so padding is
    zero exactly as :func:`..ops.blake2b.pack_payloads` guarantees.
    Positions are int32: the per-call residency cap is < 2 GiB.
    """
    width = nblocks * 128
    idx = offs[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    valid = idx < (offs + lens)[:, None]
    widx = jnp.clip(idx >> 2, 0, words.shape[0] - 1)
    w = jnp.take(words, widx, axis=0)
    byte = (w >> ((idx & 3).astype(U32) << U32(3))) & U32(0xFF)
    byte = jnp.where(valid, byte, U32(0))
    # 4 bytes -> one little-endian u32 word
    b = byte.reshape(chunk_b, nblocks * 32, 4)
    w32 = (b[:, :, 0] | (b[:, :, 1] << U32(8)) | (b[:, :, 2] << U32(16))
           | (b[:, :, 3] << U32(24)))
    w32 = w32.reshape(chunk_b, nblocks, 32)
    return w32[:, :, 1::2], w32[:, :, 0::2]  # (hi, lo)


_pack_extents_kernel = _jit_site("ops.fused_cdc_hash.pack_extents",
                                 _pack_extents_kernel)


def pack_extents_device(words, offs, lens, nblocks: int):
    """(B,) extents over a device-resident u32 word buffer -> device
    (mh, ml, lengths) in the :func:`..ops.blake2b.blake2b_packed`
    contract, without the bytes ever visiting the host."""
    B = len(offs)
    offs_h = np.asarray(offs, dtype=np.int64)
    if B and int(offs_h.max()) + nblocks * 128 >= (1 << 31):
        # int32 position arithmetic would wrap (see RESIDENCY_CAP) —
        # refuse loudly rather than gather garbage into the padding
        raise ValueError(
            f"extent pack positions exceed int32 range "
            f"(max offset {int(offs_h.max())} + padded width "
            f"{nblocks * 128}); keep residencies under RESIDENCY_CAP"
        )
    offs_d = jnp.asarray(offs_h.astype(np.int32))
    lens_d = jnp.asarray(np.asarray(lens, dtype=np.int32))
    mh, ml = _pack_extents_kernel(words, offs_d, lens_d, nblocks, B)
    return mh, ml, lens_d.astype(U32)


def hash_cuts_device(words, cuts, nbytes: int, use_pallas: bool | None = None,
                     pipeline_bytes: int = 64 << 20):
    """Chunk digests for ``cuts`` over a device-resident word buffer.

    The single-residency replacement for host ``pack_ragged`` + upload:
    extents are bucketed by power-of-two block count (the
    :func:`..batch.feed.bucketed_extents` policy), packed on device by
    :func:`pack_extents_device` in bounded pipeline chunks, and hashed
    by the batched BLAKE2b the backend routes to.  Returns ``(hh, hl)``
    device arrays, each (nchunks, 4) uint32, in cut order.
    """
    from ..batch.feed import PALLAS_MIN_CHUNK_ITEMS, bucketed_extents
    from . import blake2b

    ends = np.asarray(cuts, dtype=np.int64)
    offs = np.concatenate([np.zeros(1, np.int64), ends[:-1]])
    lens = ends - offs
    n = len(ends)
    out_hh = jnp.zeros((max(1, n), 4), dtype=jnp.uint32)
    out_hl = jnp.zeros((max(1, n), 4), dtype=jnp.uint32)
    if not n:
        return out_hh[:0], out_hl[:0]
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    fences: list = []
    donate = blake2b.donation_supported()
    for nb, idx in bucketed_extents(lens).items():
        B = len(idx)
        chunk_b = max(1, pipeline_bytes // (nb * 128))
        if use_pallas:
            chunk_b = max(chunk_b, PALLAS_MIN_CHUNK_ITEMS)
        chunk_b = blake2b._bucket_nblocks(min(chunk_b, max(1, B)))
        # donated dispatch, same routing as feed.hash_extents_device:
        # the device-packed mh/ml are consumed by exactly one program,
        # so their HBM recycles into the next chunk's pack
        if use_pallas and chunk_b >= PALLAS_MIN_CHUNK_ITEMS:
            if donate:
                from .blake2b_pallas import (
                    blake2b_packed_pallas_donated as fn,
                )
            else:
                from .blake2b_pallas import blake2b_packed_pallas as fn
        else:
            fn = (blake2b.blake2b_packed_donated if donate
                  else blake2b.blake2b_packed)
        for c0 in range(0, B, chunk_b):
            sub = idx[c0:c0 + chunk_b]
            bs = len(sub)
            po = np.zeros(chunk_b, dtype=np.int64)
            pl_ = np.zeros(chunk_b, dtype=np.int64)
            po[:bs] = offs[sub]
            pl_[:bs] = lens[sub]
            mh, ml, blens = pack_extents_device(words, po, pl_, nb)
            hh, hl = fn(mh, ml, blens)
            at = jnp.asarray(sub)
            out_hh = out_hh.at[at].set(hh[:bs, :4])
            out_hl = out_hl.at[at].set(hl[:bs, :4])
            fences.append(hh)
            while len(fences) > 2:  # bound in-flight packed batches
                np.asarray(fences.pop(0)[:1, :1])
    return out_hh, out_hl


def content_begin(buf: np.ndarray, avg_bits: int = 13,
                  min_size: int | None = None, max_size: int | None = None,
                  tile_bytes: int = 1 << 17):
    """Single-residency device content addressing for one buffer.

    Uploads the blob words ONCE; the CDC extraction (whatever
    ``DAT_CDC_ROUTE`` kernel, ``fused1p`` included) and the chunk
    BLAKE2b both read the same resident buffer — the device analogue of
    the native engine's one-sweep ``dat_cdc_hash``.  Returns a zero-arg
    ``collect()`` -> ``(cuts, hh, hl)``: cut end-offsets (host list) and
    digest word columns (DEVICE arrays, (nchunks, 4) u32 each), so a
    merkle consumer folds them without a D2H round-trip.

    Per-call limit 2 GiB (the candidate extractor's cap); multi-slab
    streams compose :func:`..ops.rabin.chunk_stream` + repeated calls.
    """
    from .rabin import _clamp_thin_bits, _greedy_select, candidates_begin

    if min_size is None:
        min_size = 1 << (avg_bits - 2)
    if max_size is None:
        max_size = 1 << (avg_bits + 2)
    nbytes = len(buf)
    thin_bits = _clamp_thin_bits(max(min_size, 1).bit_length() - 1,
                                 tile_bytes)
    staged = np.zeros(-(-nbytes // 4), dtype="<u4")
    staged.view(np.uint8)[:nbytes] = buf
    words = jnp.asarray(staged)  # the ONE upload
    cand = candidates_begin(words, nbytes, avg_bits, tile_bytes,
                            thin_bits=thin_bits)

    def collect():
        cuts = _greedy_select(cand(), nbytes, min_size, max_size)
        hh, hl = hash_cuts_device(words, cuts, nbytes)
        if _OBS.on:
            _M_FUSED_BYTES.inc(nbytes)
            _M_FUSED_CHUNKS.inc(len(cuts))
        return cuts, hh, hl

    return collect
