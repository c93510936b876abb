"""Batched BLAKE2b as a Pallas TPU kernel.

The XLA-scan formulation in :mod:`.blake2b` leaves VPU throughput on the
table: every scan step re-materializes carries and message slices through
fusion boundaries.  This kernel keeps the whole hash state resident in
VMEM scratch for the lifetime of a batch tile and streams message blocks
HBM -> VMEM with Pallas's pipelined block fetches, so the 12 unrolled
rounds run as straight-line VPU code with no per-block traffic beyond the
message bytes themselves.

Layout (TPU-first):

* The kernel reads the rows as :func:`.blake2b.stage_payloads` laid them
  and ``device_put`` shipped them: ``(rows, nblocks*32)`` little-endian
  u32, a row one item's zero-padded bytes, u32 ``2k`` the low half of
  message word ``k`` and ``2k+1`` its high half.  No copy of the staged
  words is made in HBM.
* A tile is 1,024 rows once the batch fills one, else the batch itself
  (a power of two, at least ``MIN_TILE_ITEMS``), so a few wide rows stay
  a few wide rows.  Its items sit on ``S`` sublanes x ``L`` lanes
  (``L`` = 128, or the whole tile under 128): every 64-bit lane-pair op
  of the rounds covers the tile in ``S``-sublane vector registers
  (:func:`tile_shape`).
* Grid = (tiles, steps): tiles are embarrassingly parallel; each step's
  block fetch is a ``(tile, 32*blocks)`` window of raw rows — the whole
  row where a slot is four blocks or narrower, else four or more blocks
  side by side on the lanes — and the step axis is sequential
  ("arbitrary"), the chaining state in VMEM scratch, initialized at the
  first step and emitted at the last.
* In VMEM, each ``L``-row chunk of the window is transposed (XLU) and
  stored with a sublane stride of ``S``, so u32 column ``c`` lies in rows
  ``c*S .. c*S+S`` of a scratch: one message word's half is then one
  ``(S, L)`` item tile at a row the word's index gives — the hi/lo
  de-interleave is a choice of row, not a pass over the bytes.  The
  step then compresses its blocks in order.
* Per-item variable lengths use the same active/final masks as the scan
  version (:func:`.blake2b.blake2b_packed`) — no dynamic shapes.

Round function and masks are shared with :mod:`.blake2b` (they are
shape-polymorphic), so byte-exactness is inherited from the tested scan
path.  reference: the protocol itself does no hashing (SURVEY.md §2);
this kernel serves BASELINE.json's ">= 50 GiB/s batched BLAKE2b" target.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .blake2b import (
    _IV_HI,
    _IV_LO,
    _ROUND_SIGMA,
    DIGEST_SIZE,
    MIN_TILE_ITEMS,
    compress_soa,
)
from ..obs.device import jit_site as _jit_site
from ..utils.num import next_pow2
from .u64 import U32

_LANE = 128
_TILE_ROWS = 1024  # a full tile: 8 sublanes x 128 lanes of items
_WORDS = 32  # u32 words a 128-byte block
# the raw words one grid step fetches, at most: a wide slot's window grows
# past four blocks while it stays under this, so a tile of a few rows
# walks a wide slot in fewer, larger steps
_WINDOW_BYTES = 512 << 10


class Tile(NamedTuple):
    """How the kernel walks a bucket: ``rows`` items a tile on
    ``sublanes`` x ``lanes``, ``blocks`` message blocks a grid step."""
    rows: int
    sublanes: int
    lanes: int
    blocks: int


def tile_shape(rows: int, nblocks: int) -> Tile:
    """The tile and step of a ``(rows, nblocks*32)`` batch: a full tile
    once the batch fills one, else the batch itself as ONE tile (a power
    of two, at least ``MIN_TILE_ITEMS``); a step the whole row where a
    slot is at most four blocks wide, else four blocks doubled while they
    divide the slot and the window stays within ``_WINDOW_BYTES``."""
    t = _TILE_ROWS if rows >= _TILE_ROWS else max(MIN_TILE_ITEMS,
                                                  next_pow2(rows))
    lanes = min(_LANE, t)
    if nblocks <= 4:
        blocks = nblocks
    else:
        blocks = 4
        while (nblocks % (2 * blocks) == 0
               and t * 2 * blocks * _WORDS * 4 <= _WINDOW_BYTES):
            blocks *= 2
    return Tile(t, t // lanes, lanes, blocks)


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

    Word ``w`` of the step's block ``b`` is u32 columns ``32b + 2w``
    (low half) and ``32b + 2w + 1`` (high half): ``S`` rows each of the
    transposed scratch.  A column's first row is traced once a block, not
    once a use: 32 row numbers, where the rounds load 768 times.
    """

    def __init__(self, msg_ref, b, sublanes: int):
        self._msg = msg_ref
        self._s = sublanes
        self._base = jax.lax.mul(b, np.int32(_WORDS * sublanes))
        self._rows: dict = {}

    def __getitem__(self, w):
        return self._half(2 * int(w) + 1), self._half(2 * int(w))

    def _half(self, c: int):
        row = self._rows.get(c)
        if row is None:
            row = self._rows[c] = jax.lax.add(self._base,
                                              np.int32(c * self._s))
        return self._msg[pl.ds(row, self._s), :]


def _kernel(*refs, digest_size: int, unroll: bool, sublanes: int,
            blocks: int):
    if unroll:
        len_ref, x_ref, outh_ref, outl_ref, sth_ref, stl_ref, msg_ref = refs
        sigma = None
    else:
        # scanned-rounds variant (interpreter): the schedule table rides in
        # as an input ref — pallas kernels may not capture array constants
        (len_ref, x_ref, sig_ref,
         outh_ref, outl_ref, sth_ref, stl_ref, msg_ref) = refs
        sigma = sig_ref[:]
    j = pl.program_id(1)
    lanes = len_ref.shape[1]
    width = _WORDS * blocks

    @pl.when(j == 0)
    def _init():
        shape = len_ref.shape
        param_lo = np.uint32(0x01010000 ^ digest_size)
        for w in range(8):
            sth_ref[w] = jnp.full(shape, _IV_HI[w], U32)
            lo = _IV_LO[w] ^ param_lo if w == 0 else _IV_LO[w]
            stl_ref[w] = jnp.full(shape, lo, U32)

    # the window's items onto lanes: chunk a (rows a*L .. a*L+L) becomes
    # sublane a of every column's (S, L) tile
    for a in range(sublanes):
        cols = x_ref[pl.ds(a * lanes, lanes), :].T
        if sublanes == 1:
            msg_ref[...] = cols
        else:
            msg_ref[pl.ds(a, width, stride=sublanes), :] = cols

    lengths = len_ref[:]
    # where-based max/min: Mosaic has no arith.maxui/minui legalization
    nb_ceil = (lengths + U32(127)) >> U32(7)
    item_blocks = jnp.where(nb_ceil == U32(0), U32(1), nb_ceil)

    def compress_block(b, carry):
        k = (j * blocks + b).astype(U32)
        active = k < item_blocks
        final = k == item_blocks - U32(1)
        cap = (k + U32(1)) << U32(7)
        t_lo = jnp.where(cap < lengths, cap, lengths)
        m = _RefWords(msg_ref, b, sublanes)
        if not unroll:
            m = [m[w] for w in range(16)]
        h = [(sth_ref[w], stl_ref[w]) for w in range(8)]
        nh = compress_soa(h, m, t_lo, final, unroll=unroll, sigma=sigma)
        for w in range(8):
            sth_ref[w] = jnp.where(active, nh[w][0], h[w][0])
            stl_ref[w] = jnp.where(active, nh[w][1], h[w][1])
        return carry

    jax.lax.fori_loop(0, blocks, compress_block, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _emit():
        for w in range(8):
            outh_ref[w] = sth_ref[w]
            outl_ref[w] = stl_ref[w]


def _compress_rows(words, lengths, digest_size: int, interpret: bool):
    """The kernel over ``words`` ``(rows, nblocks*32)`` u32, ``rows`` a
    whole number of :func:`tile_shape` tiles and a slot four blocks wide
    or less or a multiple of four; ``lengths`` ``(rows,)``.  Returns the
    digest words ``(hh, hl)``, each ``(rows, 8)``."""
    rows, width = words.shape
    nb = width // _WORDS
    tile = tile_shape(rows, nb)
    if rows % tile.rows or (nb > 4 and nb % 4):
        raise ValueError(f"words {words.shape} are not whole tiles {tile}")
    if lengths.shape != (rows,):
        raise ValueError(
            f"words {words.shape} do not hold lengths {lengths.shape}")
    n_tiles = rows // tile.rows
    item_tile = (tile.sublanes, tile.lanes)
    step = _WORDS * tile.blocks
    # Mosaic gets the straight-line unrolled rounds; the interpreter (CPU
    # tests) gets the scanned rounds, whose 12x-smaller graph sidesteps
    # the CPU backend's pathological compile of the unrolled chain
    unroll = not interpret
    kernel = functools.partial(
        _kernel, digest_size=digest_size, unroll=unroll,
        sublanes=tile.sublanes, blocks=tile.blocks,
    )
    in_specs = [pl.BlockSpec(item_tile, lambda i, j: (i, 0)),
                pl.BlockSpec((tile.rows, step), lambda i, j: (i, j))]
    inputs = [lengths.reshape(n_tiles * tile.sublanes, tile.lanes), words]
    if not unroll:
        in_specs.append(pl.BlockSpec((12, 16), lambda i, j: (0, 0)))
        inputs.append(jnp.asarray(np.stack(_ROUND_SIGMA)))
    out_shape = jax.ShapeDtypeStruct(
        (8, n_tiles * tile.sublanes, tile.lanes), jnp.uint32)
    outh, outl = pl.pallas_call(
        kernel,
        grid=(n_tiles, nb // tile.blocks),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((8,) + item_tile, lambda i, j: (0, i, 0))] * 2,
        out_shape=[out_shape, out_shape],
        scratch_shapes=[pltpu.VMEM((8,) + item_tile, jnp.uint32)] * 2
        + [pltpu.VMEM((step * tile.sublanes, tile.lanes), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the name the device trace shows this kernel under
        name="blake2b_compress",
    )(*inputs)
    # item r of tile i sits at (i*S + r // L, r % L): row-major, as staged
    return outh.reshape(8, rows).T, outl.reshape(8, rows).T


def _hash_rows(words, lengths, digest_size: int, interpret: bool):
    B, width = words.shape
    nb = width // _WORDS
    per = tile_shape(B, nb).rows
    rows = -(-B // per) * per
    cols = width if nb <= 4 or nb % 4 == 0 else -(-nb // 4) * 4 * _WORDS
    if (rows, cols) != (B, width):
        # zero rows are empty payloads, zero blocks past a length inactive
        words = jnp.pad(words, ((0, rows - B), (0, cols - width)))
        lengths = jnp.pad(lengths, (0, rows - B))
    hh, hl = _compress_rows(words, lengths.astype(U32), digest_size,
                            interpret)
    return hh[:B], hl[:B]


def blake2b_words_pallas(words, lengths, digest_size: int = DIGEST_SIZE,
                         interpret: bool = False):
    """BLAKE2b of raw staged rows: ``words`` is ``(B, nblocks*32)``
    uint32 as :func:`.blake2b.stage_payloads` lays them, ``lengths``
    ``(B,)``; returns ``(hh, hl)``, each ``(B, 8)``.  The kernel reads
    the rows where they lie — one ``jit_blake2b_words_pallas`` program a
    ``(B, nblocks)`` bucket, with no layout copy ahead of the kernel.
    Only a batch that is not whole tiles (no declared row count), or a
    slot wider than four blocks and not a multiple of four, is padded
    first."""
    return _hash_rows(words, lengths, digest_size, interpret)


def blake2b_packed_pallas(mh, ml, lengths, digest_size: int = DIGEST_SIZE,
                          interpret: bool = False):
    """Drop-in for :func:`.blake2b.blake2b_packed`, Pallas-accelerated.

    Same (B, nblocks, 16) hi/lo interface and (B, 8) hi/lo digest
    outputs: the halves are interleaved back into raw rows on the device,
    then hashed by the one kernel as :func:`blake2b_words_pallas` does.
    """
    words = jnp.stack([ml, mh], axis=-1).reshape(mh.shape[0], -1)
    return _hash_rows(words, lengths, digest_size, interpret)


_STATIC = ("digest_size", "interpret")
# donated twin (see blake2b.blake2b_packed_donated): one jit over the
# interleave and the kernel so that the staged (B, nblocks, 16) message
# buffers are donated into the program and their HBM recycles into the
# next batch's staging — the double-buffered upload discipline
blake2b_packed_pallas_donated = _jit_site(
    "ops.blake2b_pallas.packed_donated",
    functools.partial(jax.jit, static_argnames=_STATIC,
                      donate_argnums=(0, 1))(blake2b_packed_pallas),
)
# the donated twin first: it wraps the function, and the name is about
# to be rebound to the plain twin's jit site
blake2b_words_pallas_donated = _jit_site(
    "ops.blake2b_pallas.words_donated",
    functools.partial(jax.jit, static_argnames=_STATIC,
                      donate_argnums=(0,))(blake2b_words_pallas),
)
blake2b_words_pallas = _jit_site(
    "ops.blake2b_pallas.words",
    functools.partial(jax.jit, static_argnames=_STATIC)(
        blake2b_words_pallas),
)
