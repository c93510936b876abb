"""Batched BLAKE2b as a Pallas TPU kernel.

The XLA-scan formulation in :mod:`.blake2b` leaves VPU throughput on the
table: every scan step re-materializes carries and message slices through
fusion boundaries.  This kernel keeps the whole hash state resident in
VMEM scratch for the lifetime of a batch tile and streams message blocks
HBM -> VMEM with Pallas's pipelined block fetches, so the 12 unrolled
rounds run as straight-line VPU code with no per-block traffic beyond the
message bytes themselves.

Layout (TPU-first):

* Mosaic tiles are (8, 128) for uint32, so the batch axis is reshaped to
  ``(8, B/8)`` — every 64-bit lane-pair op covers full vector registers.
* Messages arrive pre-packed as ``(nblocks, 16, 8, B/8)`` hi/lo uint32
  (word-major), so each of the 16 message words is one contiguous
  ``(8, BTL)`` tile slice: zero strided reads in the hot loop.
* Grid = (batch_tiles, nblocks): batch tiles are embarrassingly parallel;
  the block axis is sequential ("arbitrary") with the chaining state in
  VMEM scratch, initialized at block 0 and emitted at the last block.
* A batch under one 1,024-item tile is ONE tile in a packed layout,
  ``(nblocks, 16*S, L)`` with ``S x L`` items (``L`` = 128 lanes, or the
  whole batch under 128): the word axis shares the sublane axis, so HBM
  holds no padded sublanes and a few wide rows stay a few wide rows
  (:func:`tile_items`, :func:`to_native`).
* Per-item variable lengths use the same active/final masks as the scan
  version (:func:`.blake2b.blake2b_packed`) — no dynamic shapes.

Round function and masks are shared with :mod:`.blake2b` (they are
shape-polymorphic), so byte-exactness is inherited from the tested scan
path.  reference: the protocol itself does no hashing (SURVEY.md §2);
this kernel serves BASELINE.json's ">= 50 GiB/s batched BLAKE2b" target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blake2b import (
    _IV_HI,
    _IV_LO,
    DIGEST_SIZE,
    MIN_TILE_ITEMS,
    compress_soa,
    split_words,
)
from ..obs.device import jit_site as _jit_site
from ..utils.num import next_pow2
from .u64 import U32

# batch items per kernel tile: 8 sublanes x BTL lanes
_LANE = 128
_SUBLANE = 8


def tile_items(B: int, block_items: int = 1024) -> int:
    """Rows one kernel tile holds for a batch of ``B``: ``block_items``
    once the batch fills a tile, else the batch itself (a power of two,
    at least ``MIN_TILE_ITEMS``) as ONE packed tile — what keeps a few
    wide rows from being padded to 1,024 of them."""
    if B >= block_items:
        return block_items
    return max(MIN_TILE_ITEMS, next_pow2(B))


def _packed_shape(items: int) -> tuple[int, int]:
    """(sublanes S, lanes L) of a packed single tile of ``items``."""
    lanes = min(_LANE, items)
    return items // lanes, lanes


class _RefWords:
    """Lazy message-word view: ``m[w]`` issues the VMEM loads at use site.

    The unrolled rounds reference each of the 16 message words twice per
    round; materializing all 32 hi/lo word tiles up front pins 32 vector
    registers for the whole block, which together with the 32 state
    registers overflows the register file and makes the scheduler spill
    *state* (measured: block_items=2048 halves throughput).  Issuing the
    loads where the schedule consumes them leaves liveness decisions to
    Mosaic, which can rematerialize a cheap VMEM load instead of
    spilling a hot value.
    """

    def __init__(self, mh_ref, ml_ref, packed: int):
        self._mh = mh_ref
        self._ml = ml_ref
        self._packed = packed

    def __getitem__(self, w):
        return _word(self._mh, self._ml, int(w), self._packed)


def _word(mh_ref, ml_ref, w: int, packed: int):
    """Message word ``w`` of the step's block as a (hi, lo) pair of item
    tiles: ``ref[0, w]`` of a ``(1, 16, 8, BTL)`` block, or — in a
    packed single tile of ``packed`` sublanes — rows ``w*S .. w*S+S`` of
    a ``(1, 16*S, L)`` block."""
    if not packed:
        return mh_ref[0, w], ml_ref[0, w]
    rows = pl.ds(w * packed, packed)
    return mh_ref[0, rows, :], ml_ref[0, rows, :]


def _kernel(*refs, digest_size: int, unroll: bool = True, packed: int = 0):
    if unroll:
        len_ref, mh_ref, ml_ref, outh_ref, outl_ref, sth_ref, stl_ref = refs
        sigma = None
    else:
        # scanned-rounds variant (interpreter): the schedule table rides in
        # as an input ref — pallas kernels may not capture array constants
        (len_ref, mh_ref, ml_ref, sig_ref,
         outh_ref, outl_ref, sth_ref, stl_ref) = refs
        sigma = sig_ref[:]
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        shape = len_ref.shape
        param_lo = np.uint32(0x01010000 ^ digest_size)
        for w in range(8):
            sth_ref[w] = jnp.full(shape, _IV_HI[w], U32)
            lo = _IV_LO[w] ^ param_lo if w == 0 else _IV_LO[w]
            stl_ref[w] = jnp.full(shape, lo, U32)

    lengths = len_ref[:]
    # where-based max/min: Mosaic has no arith.maxui/minui legalization
    nb_ceil = (lengths + U32(127)) >> U32(7)
    item_blocks = jnp.where(nb_ceil == U32(0), U32(1), nb_ceil)

    ju = j.astype(U32)
    active = ju < item_blocks
    final = ju == item_blocks - U32(1)
    cap = (ju + U32(1)) << U32(7)
    t_lo = jnp.where(cap < lengths, cap, lengths)

    if unroll:
        m = _RefWords(mh_ref, ml_ref, packed)
    else:
        m = [_word(mh_ref, ml_ref, w, packed) for w in range(16)]
    h = [(sth_ref[w], stl_ref[w]) for w in range(8)]
    nh = compress_soa(h, m, t_lo, final, unroll=unroll, sigma=sigma)
    for w in range(8):
        sth_ref[w] = jnp.where(active, nh[w][0], h[w][0])
        stl_ref[w] = jnp.where(active, nh[w][1], h[w][1])

    @pl.when(j == nb - 1)
    def _emit():
        for w in range(8):
            outh_ref[w] = sth_ref[w]
            outl_ref[w] = stl_ref[w]


@functools.partial(
    jax.jit,
    static_argnames=("digest_size", "block_items", "interpret"),
)
def blake2b_native(mh, ml, lengths, digest_size: int = DIGEST_SIZE,
                   block_items: int = 1024, interpret: bool = False):
    """Hash in the kernel-native layout.

    ``mh``/``ml``: (nblocks, 16, 8, B/8) uint32 message word halves;
    ``lengths``: (8, B/8) uint32.  ``B`` must be a multiple of
    ``block_items`` (and ``block_items`` of 8*128).  Returns digest words
    as ``(hh, hl)``, each (8, 8, B/8): word-major, batch split like the
    input.

    A batch under one tile arrives packed (:func:`to_native`):
    ``mh``/``ml`` (nblocks, 16*S, L), ``lengths`` (S, L), and the digest
    words come back (8, S, L); ``block_items`` is not consulted.
    """
    packed = lengths.shape[0] if mh.ndim == 3 else 0
    if packed:
        # ONE tile: (S, L) items, the word axis folded into the sublanes
        nb = mh.shape[0]
        tile = lengths.shape
        if mh.shape != (nb, 16 * tile[0], tile[1]):
            raise ValueError(
                f"packed words {mh.shape} do not hold lengths {tile}")
        n_tiles = 1
    else:
        nb, _, s, bl = mh.shape
        if s != _SUBLANE:
            raise ValueError(
                f"batch must be split (8, B/8); got sublane {s}")
        if block_items % (_SUBLANE * _LANE):
            raise ValueError(
                f"block_items must be a multiple of {_SUBLANE * _LANE}")
        btl = block_items // _SUBLANE
        if bl % btl:
            raise ValueError(f"B/8={bl} not a multiple of tile width {btl}")
        tile = (_SUBLANE, btl)
        n_tiles = bl // btl
    grid = (n_tiles, nb)
    # Mosaic gets the straight-line unrolled rounds; the interpreter (CPU
    # tests) gets the scanned rounds, whose 12x-smaller graph sidesteps
    # the CPU backend's pathological compile of the unrolled chain
    unroll = not interpret
    kernel = functools.partial(
        _kernel, digest_size=digest_size, unroll=unroll, packed=packed,
    )
    if packed:
        msg_spec = pl.BlockSpec((1,) + mh.shape[1:], lambda i, j: (j, 0, 0))
    else:
        msg_spec = pl.BlockSpec((1, 16) + tile, lambda i, j: (j, 0, 0, i))
    in_specs = [pl.BlockSpec(tile, lambda i, j: (0, i)), msg_spec, msg_spec]
    inputs = [lengths, mh, ml]
    if not unroll:
        from .blake2b import _ROUND_SIGMA

        in_specs.append(pl.BlockSpec((12, 16), lambda i, j: (0, 0)))
        inputs.append(jnp.asarray(np.stack(_ROUND_SIGMA)))
    out_shape = jax.ShapeDtypeStruct((8, tile[0], tile[1] * n_tiles),
                                     jnp.uint32)
    outh, outl = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((8,) + tile, lambda i, j: (0, 0, i))] * 2,
        out_shape=[out_shape, out_shape],
        scratch_shapes=[pltpu.VMEM((8,) + tile, jnp.uint32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the name the device trace shows this kernel under
        name="blake2b_compress",
    )(*inputs)
    return outh, outl


# recompile sentinel: the kernel specializes per (nblocks, B) tile shape
blake2b_native = _jit_site("ops.blake2b_pallas.native", blake2b_native)


def to_native(mh, ml, lengths, block_items: int = 1024):
    """(B, nblocks, 16) padded-batch layout -> kernel-native layout.

    Pads the batch up to whole tiles of :func:`tile_items` rows (zero
    payloads are valid BLAKE2b inputs; the wrapper drops their digests).
    Returns (mh_n, ml_n, lengths_n, B): (nblocks, 16, 8, Bp/8) words and
    (8, Bp/8) lengths for whole 1,024-item tiles, the packed
    (nblocks, 16*S, L) and (S, L) for a batch under one.

    ``mh``/``ml`` arrive already split: by the host
    (:func:`.blake2b.pack_payloads`) for :func:`blake2b_packed_pallas`'s
    callers, or by :func:`.blake2b.split_words` earlier in the same
    program for :func:`blake2b_words_pallas`, where the split and these
    transposes are the program's layout copies ahead of the kernel.
    """
    B, nb, _ = mh.shape
    tile = tile_items(B, block_items)
    Bp = -(-B // tile) * tile
    if Bp != B:
        mh = jnp.pad(mh, ((0, Bp - B), (0, 0), (0, 0)))
        ml = jnp.pad(ml, ((0, Bp - B), (0, 0), (0, 0)))
        lengths = jnp.pad(lengths, (0, Bp - B))
    if tile < block_items:
        # ONE packed tile: item i sits at (i // L, i % L), and a word's S
        # sublanes follow the word before it
        s, lanes = _packed_shape(tile)
        shape, len_shape = (nb, 16 * s, lanes), (s, lanes)
    else:
        shape = (nb, 16, _SUBLANE, Bp // _SUBLANE)
        len_shape = shape[2:]
    mh_n = jnp.transpose(mh, (1, 2, 0)).reshape(shape)
    ml_n = jnp.transpose(ml, (1, 2, 0)).reshape(shape)
    return mh_n, ml_n, lengths.reshape(len_shape), B


def from_native(outh, outl, B: int):
    """Kernel-native digest words -> (B, 8) hi/lo (the scan-path layout)."""
    Bp = outh.shape[1] * outh.shape[2]
    hh = outh.reshape(8, Bp).T[:B]
    hl = outl.reshape(8, Bp).T[:B]
    return hh, hl


def blake2b_packed_pallas(mh, ml, lengths, digest_size: int = DIGEST_SIZE,
                          block_items: int = 1024, interpret: bool = False):
    """Drop-in for :func:`.blake2b.blake2b_packed`, Pallas-accelerated.

    Same (B, nblocks, 16) interface and (B, 8) hi/lo digest outputs.
    """
    mh_n, ml_n, len_n, B = to_native(mh, ml, lengths, block_items)
    outh, outl = blake2b_native(
        mh_n, ml_n, len_n, digest_size, block_items, interpret
    )
    return from_native(outh, outl, B)


# donated twin (see blake2b.blake2b_packed_donated): one jit over the
# whole layout-transpose + kernel chain so the staged (B, nblocks, 16)
# message buffers are donated into the program and their HBM recycles
# into the next batch's staging — the double-buffered upload discipline
blake2b_packed_pallas_donated = functools.partial(
    jax.jit,
    static_argnames=("digest_size", "block_items", "interpret"),
    donate_argnums=(0, 1),
)(blake2b_packed_pallas)
blake2b_packed_pallas_donated = _jit_site(
    "ops.blake2b_pallas.packed_donated", blake2b_packed_pallas_donated
)


def blake2b_words_pallas(words, lengths, digest_size: int = DIGEST_SIZE,
                         block_items: int = 1024, interpret: bool = False):
    """:func:`blake2b_packed_pallas` over raw staged words: ``words`` is
    ``(B, nblocks*32)`` uint32 as :func:`.blake2b.stage_payloads` lays
    them, split hi/lo here — split, transposes and kernel are one
    ``jit_blake2b_words_pallas`` program per ``(B, nblocks)`` bucket."""
    mh, ml = split_words(words)
    return blake2b_packed_pallas(mh, ml, lengths, digest_size, block_items,
                                 interpret)


_WORDS_STATIC = ("digest_size", "block_items", "interpret")
# the donated twin first: it wraps the function, and the name is about
# to be rebound to the plain twin's jit site
blake2b_words_pallas_donated = _jit_site(
    "ops.blake2b_pallas.words_donated",
    functools.partial(jax.jit, static_argnames=_WORDS_STATIC,
                      donate_argnums=(0,))(blake2b_words_pallas),
)
blake2b_words_pallas = _jit_site(
    "ops.blake2b_pallas.words",
    functools.partial(jax.jit, static_argnames=_WORDS_STATIC)(
        blake2b_words_pallas),
)
