"""Merkle tree-level hashing as a Pallas TPU kernel.

A tree level is the ideal Pallas shape: every parent is exactly ONE
BLAKE2b compression of a fixed 64-byte two-child message (level 0 of the
1M-leaf BASELINE.json config is a 524288-item batch).  The general batched
kernel (:mod:`.blake2b_pallas`) spends its flexibility on variable
lengths, multi-block chaining, and VMEM state carried across a grid
axis; none of that applies here, so this kernel is the stripped-down
single-block form: no lengths, no masks, no scratch, no block axis —
just IV init, 12 unrolled rounds, and the finalizing XOR, over full
(8, 128) uint32 vregs.

This is the round-3 replacement for the scanned-rounds compromise the
tree build used to make for compile time (``merkle_parent``'s ~2x
runtime cost, ops/merkle.py): levels big enough to matter go through
this kernel; tiny top levels keep the scanned XLA path where compile
time, not throughput, binds.

reference: the protocol has no Merkle machinery (SURVEY.md §2 — dat core
holds it above the wire); this serves BASELINE.json's ">= 10M diff
entries/sec" target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .blake2b import _IV_HI, _IV_LO, _ROUND_SIGMA, compress_soa
from .merkle import DIGEST_SIZE
from .u64 import U32
from ..obs.device import jit_site as _jit_site

_LANE = 128
_SUBLANE = 8


def _kernel(*refs, unroll: bool):
    if unroll:
        mh_ref, ml_ref, outh_ref, outl_ref = refs
        sigma = None
    else:
        mh_ref, ml_ref, sig_ref, outh_ref, outl_ref = refs
        sigma = sig_ref[:]
    shape = mh_ref.shape[1:]  # (8, btl)
    zero = jnp.zeros(shape, U32)
    m = [(mh_ref[w], ml_ref[w]) for w in range(8)]
    m += [(zero, zero)] * 8  # the 64-byte message fills half the block
    param_lo = np.uint32(0x01010000 ^ DIGEST_SIZE)
    h = []
    for w in range(8):
        lo = _IV_LO[w] ^ param_lo if w == 0 else _IV_LO[w]
        h.append((jnp.full(shape, _IV_HI[w], U32), jnp.full(shape, lo, U32)))
    t_lo = jnp.full(shape, np.uint32(2 * DIGEST_SIZE), U32)
    final = jnp.ones(shape, dtype=bool)
    nh = compress_soa(h, m, t_lo, final, unroll=unroll, sigma=sigma)
    for w in range(4):
        outh_ref[w] = nh[w][0]
        outl_ref[w] = nh[w][1]


@functools.partial(
    jax.jit, static_argnames=("block_items", "interpret")
)
def merkle_level_native(mh, ml, block_items: int = 1024,
                        interpret: bool = False):
    """``mh``/``ml``: (8, 8, P/8) uint32 message word halves (the two
    children's 4 word-pairs each) -> parent digests (4, 8, P/8)."""
    w, s, pl_ = mh.shape
    if w != 8 or s != _SUBLANE:
        raise ValueError(f"expected (8, 8, P/8); got {mh.shape}")
    if block_items % (_SUBLANE * _LANE):
        raise ValueError(f"block_items must be a multiple of {_SUBLANE * _LANE}")
    btl = block_items // _SUBLANE
    if pl_ % btl:
        raise ValueError(f"P/8={pl_} not a multiple of tile width {btl}")

    unroll = not interpret
    kernel = functools.partial(_kernel, unroll=unroll)
    in_specs = [
        pl.BlockSpec((8, _SUBLANE, btl), lambda i: (0, 0, i)),
        pl.BlockSpec((8, _SUBLANE, btl), lambda i: (0, 0, i)),
    ]
    inputs = [mh, ml]
    if not unroll:
        in_specs.append(pl.BlockSpec((12, 16), lambda i: (0, 0)))
        inputs.append(jnp.asarray(np.stack(_ROUND_SIGMA)))
    outh, outl = pl.pallas_call(
        kernel,
        grid=(pl_ // btl,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((4, _SUBLANE, btl), lambda i: (0, 0, i)),
            pl.BlockSpec((4, _SUBLANE, btl), lambda i: (0, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((4, _SUBLANE, pl_), jnp.uint32),
            jax.ShapeDtypeStruct((4, _SUBLANE, pl_), jnp.uint32),
        ],
        interpret=interpret,
        # the name the device trace shows this kernel under
        name="merkle_level",
    )(*inputs)
    return outh, outl


merkle_level_native = _jit_site("ops.merkle_pallas.level", merkle_level_native)


def merkle_level_pallas(hh, hl, block_items: int = 1024,
                        interpret: bool = False):
    """Drop-in for :func:`.merkle.merkle_level`: (N, 4) digests ->
    (N//2, 4) parents, Pallas-accelerated.

    Children pair even/odd rows (dat's flat in-order convention, same as
    the scanned path).  Pads the parent count up to ``block_items``
    (zero-digest children are valid messages; padding parents are
    dropped).
    """
    n = hh.shape[0]
    P = n // 2
    Pp = -(-P // block_items) * block_items
    # (N, 4) -> (P, 8): row p = left child words || right child words
    mw_h = hh.reshape(P, 8)
    mw_l = hl.reshape(P, 8)
    if Pp != P:
        mw_h = jnp.pad(mw_h, ((0, Pp - P), (0, 0)))
        mw_l = jnp.pad(mw_l, ((0, Pp - P), (0, 0)))
    mh = jnp.transpose(mw_h, (1, 0)).reshape(8, _SUBLANE, Pp // _SUBLANE)
    ml = jnp.transpose(mw_l, (1, 0)).reshape(8, _SUBLANE, Pp // _SUBLANE)
    outh, outl = merkle_level_native(mh, ml, block_items, interpret)
    ph = jnp.transpose(outh.reshape(4, Pp), (1, 0))[:P]
    pdl = jnp.transpose(outl.reshape(4, Pp), (1, 0))[:P]
    return ph, pdl
