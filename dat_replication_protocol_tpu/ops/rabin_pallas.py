"""Gear CDC rolling hash as a Pallas TPU kernel.

Same algorithm as :func:`.rabin.gear_candidates_tiled` (the portable
XLA-scan path), restructured like :mod:`.blake2b_pallas`: the 64-bit
rolling-hash state lives in VMEM scratch across a tile's whole byte
range, message words stream HBM -> VMEM via pipelined block fetches, and
the per-group byte loop is straight-line unrolled VPU code — XLA's scan
scheduling leaves the serial gear chain ~30x slower than Mosaic's.

Layouts mirror the BLAKE2b kernel: the tile axis is split ``(8, T/8)``
to fill (8, 128) uint32 vregs, inputs are word-major
``(ngroups, GROUP/4, 8, T/8)``, outputs are packed candidate bitmasks
``(ngroups, GROUP/32, 8, T/8)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .rabin import GROUP, NO_HIT, PACK, _gear_step, _popcount32
from .u64 import U32
from ..obs.device import jit_site as _jit_site

_SUBLANE = 8
_LANE = 128


def _kernel(wref, oref, sth_ref, stl_ref, *, avg_bits: int, ilp: int,
            diag: str = ""):
    """``ilp`` independent lane-chunks are updated per unrolled byte step.

    The gear chain is strictly serial per lane (each byte's state update
    depends on the previous byte's), so a single chain runs at VPU
    *latency*, not throughput.  Interleaving K independent chunks in the
    instruction stream pipelines K chains through the VPU — classic
    software ILP, done manually because Mosaic schedules within, not
    across, whole-array ops.

    ``diag`` (measurement-only; output is WRONG under any non-empty
    value) carves one suspect out of the loop so a device sweep can
    attribute the kernel's ceiling by elimination:
    ``'nomul'`` replaces the two u32 multiplies with adds, ``'nostore'``
    drops the packed-mask stores and their lane concatenates,
    ``'noextract'`` skips the byte shift/mask unpack.
    """
    j = pl.program_id(1)
    mask = U32((1 << avg_bits) - 1)
    btl = sth_ref.shape[-1] // ilp

    @pl.when(j == 0)
    def _init():
        sth_ref[0] = jnp.zeros(sth_ref.shape[1:], U32)
        stl_ref[0] = jnp.zeros(stl_ref.shape[1:], U32)

    def chunk(a, k):
        return a[:, k * btl : (k + 1) * btl]

    def step(hh, hl, byte):
        if diag == "nomul":
            from .rabin import _C1, _C2

            v = byte + U32(1)
            gl = v + U32(int(_C1))
            gh = v + U32(int(_C2))
            sh = (hh << U32(1)) | (hl >> U32(31))
            sl = hl << U32(1)
            lo = sl + gl
            carry = (lo < sl).astype(U32)
            return sh + gh + carry, lo
        return _gear_step(hh, hl, byte)

    hh = [chunk(sth_ref[0], k) for k in range(ilp)]
    hl = [chunk(stl_ref[0], k) for k in range(ilp)]
    acc = [jnp.zeros_like(hh[0]) for _ in range(ilp)]
    bit = 0
    pword = 0
    for w in range(GROUP // 4):
        word = wref[0, w]
        for s in range(4):
            for k in range(ilp):
                if diag == "noextract":
                    byte = chunk(word, k)
                else:
                    byte = (chunk(word, k) >> U32(8 * s)) & U32(0xFF)
                hh[k], hl[k] = step(hh[k], hl[k], byte)
                hit = (hh[k] & mask) == U32(0)
                acc[k] = acc[k] | (hit.astype(U32) << U32(bit))
            bit += 1
            if bit == PACK:
                if diag != "nostore":
                    oref[0, pword] = jnp.concatenate(acc, axis=-1)
                acc = [jnp.zeros_like(hh[0]) for _ in range(ilp)]
                bit = 0
                pword += 1
    if diag == "nostore":  # one write keeps the block defined
        oref[0, 0] = jnp.concatenate(acc, axis=-1)
    sth_ref[0] = jnp.concatenate(hh, axis=-1)
    stl_ref[0] = jnp.concatenate(hl, axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("avg_bits", "block_tiles", "interpret", "ilp", "diag"),
)
def gear_candidates_native(words, avg_bits: int = 13,
                           block_tiles: int = 8192, interpret: bool = False,
                           ilp: int = 8, diag: str = ""):
    """``words``: (ngroups, GROUP/4, 8, T/8) uint32 -> packed bitmask
    ``(ngroups, GROUP/PACK, 8, T/8)``; bit for byte j of tile t is word
    ``j//PACK`` bit ``j%PACK`` at the tile's (sublane, lane) slot.
    """
    if diag not in ("", "nomul", "nostore", "noextract"):
        # a typo'd diag silently timing the baseline would poison the
        # by-elimination sweep captured in a scarce TPU window
        raise ValueError(f"unknown diag variant {diag!r}")
    ng, gw, s, tl = words.shape
    if gw != GROUP // 4 or s != _SUBLANE:
        raise ValueError(f"expected (ng, {GROUP // 4}, 8, T/8); got {words.shape}")
    if block_tiles % (_SUBLANE * _LANE):
        raise ValueError(f"block_tiles must be a multiple of {_SUBLANE * _LANE}")
    btl = block_tiles // _SUBLANE
    if tl % btl:
        raise ValueError(f"T/8={tl} not a multiple of tile width {btl}")

    if btl % ilp or (btl // ilp) % _LANE:
        raise ValueError(
            f"block_tiles/8={btl} must split into {ilp} lane-multiples"
        )
    grid = (tl // btl, ng)
    kernel = functools.partial(_kernel, avg_bits=avg_bits, ilp=ilp, diag=diag)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, gw, _SUBLANE, btl), lambda i, j: (j, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec(
            (1, GROUP // PACK, _SUBLANE, btl), lambda i, j: (j, 0, 0, i)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (ng, GROUP // PACK, _SUBLANE, tl), jnp.uint32
        ),
        scratch_shapes=[
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the name the device trace shows this kernel under
        name="gear_bits",
    )(words)


_SENT_OFF = 1 << 30  # "window has no candidate" (matches rabin's sentinel)


def _to_native_layout(words, block_tiles: int | None, ilp: int | None):
    """Shared wrapper boilerplate for the three kernel routes: pick
    block_tiles/ilp defaults, pad the tile count, and transpose (T, S/4)
    rows into the word-major (ng, GROUP/4, 8, Tp/8) kernel layout.
    Returns (native, Tp, ng, block_tiles, ilp)."""
    T, nwords = words.shape
    if block_tiles is None:
        block_tiles = 1024
        while block_tiles < min(T, 8192):
            block_tiles <<= 1
    if ilp is None:
        ilp = max(1, block_tiles // 1024)
    S = nwords * 4
    if S % GROUP:
        raise ValueError(f"tile bytes must be a multiple of {GROUP}")
    Tp = -(-T // block_tiles) * block_tiles
    if Tp != T:
        words = jnp.pad(words, ((0, Tp - T), (0, 0)))
    ng = S // GROUP
    # (T, ng, GROUP/4) -> (ng, GROUP/4, T) word-major -> split tile axis
    native = jnp.transpose(
        words.reshape(Tp, ng, GROUP // 4), (1, 2, 0)
    ).reshape(ng, GROUP // 4, _SUBLANE, Tp // _SUBLANE)
    return native, Tp, ng, block_tiles, ilp


def _kernel_wfirst(wref, oref, sth_ref, stl_ref, fidx_ref, fval_ref, *,
                   avg_bits: int, ilp: int, gpw: int):
    """Gear scan with the per-window first-candidate reduction FUSED in.

    Same byte loop as :func:`_kernel`, but instead of storing the packed
    bitmask (1 bit/byte, re-read by a separate reduction dispatch), the
    kernel tracks — per tile lane, in registers — the first nonzero
    packed word of the current ``2**thin_bits``-byte window
    (``gpw`` = groups per window) and flushes ONE u32 per window: the
    in-window byte offset of the first candidate, or ``_SENT_OFF``.
    Output volume drops 8x vs the bitmask (4 B per window vs 4 B per 32
    bytes) and the mask never round-trips through HBM.  The tracking
    cost is ~5 ops per packed word (per 32 bytes), off the gear chain's
    serial path; the lsb/popcount runs once per window flush.

    Window accounting: group 0 is the warm-up prefix (excluded); window
    w covers groups [1 + w*gpw, 1 + (w+1)*gpw).  ``fidx`` holds the
    window-word index (0..gpw*8-1) of the first hit, ``fval`` that
    word's bits; both persist across grid steps in VMEM scratch.
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

    def chunk(a, k):
        return a[:, k * btl : (k + 1) * btl]

    hh = [chunk(sth_ref[0], k) for k in range(ilp)]
    hl = [chunk(stl_ref[0], k) for k in range(ilp)]
    fidx = [chunk(fidx_ref[0], k) for k in range(ilp)]
    fval = [chunk(fval_ref[0], k) for k in range(ilp)]
    valid = j > 0  # group 0 is warm-up context: hits there never count
    wphase = jnp.mod(j - 1, gpw).astype(U32)  # window-local group index

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
        fidx_ref[0] = jnp.full(fidx_ref.shape[1:], sent, U32)

    @pl.when(jnp.logical_not(is_flush))
    def _keep():
        fidx_ref[0] = jnp.concatenate(fidx, axis=-1)
        fval_ref[0] = jnp.concatenate(fval, axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("avg_bits", "thin_bits", "block_tiles", "interpret",
                     "ilp"),
)
def gear_window_first_native(words, avg_bits: int, thin_bits: int,
                             block_tiles: int = 8192,
                             interpret: bool = False, ilp: int = 8):
    """``words``: (ng, GROUP/4, 8, T/8) uint32 (group 0 = warm-up) ->
    per-window first-candidate byte offsets ``(nwin_per_tile, 8, T/8)``
    uint32 (``_SENT_OFF`` = empty window)."""
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
    kernel = functools.partial(_kernel_wfirst, avg_bits=avg_bits, ilp=ilp,
                               gpw=gpw)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, gw, _SUBLANE, btl), lambda i, j: (j, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec(
            (1, _SUBLANE, btl),
            # groups [1 + w*gpw, 1 + (w+1)*gpw) -> window block w; the
            # warm-up step j=0 aliases harmlessly onto block 0 (clamped),
            # which it never writes
            lambda i, j: (jnp.maximum((j - 1) // gpw, 0), 0, i),
        ),
        out_shape=jax.ShapeDtypeStruct((nwpt, _SUBLANE, tl), jnp.uint32),
        scratch_shapes=[
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
        name="gear_window_first",
    )(words)


@functools.partial(
    jax.jit,
    static_argnames=("avg_bits", "thin_bits", "block_tiles", "interpret",
                     "ilp"),
)
def gear_window_first_pallas(words, avg_bits: int, thin_bits: int,
                             block_tiles: int | None = None,
                             interpret: bool = False, ilp: int | None = None):
    """Fused-extraction route: (T, S/4) prefixed tile rows in (group 0 =
    warm-up, as built by rabin._build_rows), stream-ordered per-window
    first-candidate offsets out — ``(T * nwin_per_tile,)`` int32 with
    ``_SENT_OFF`` for empty windows."""
    T, _ = words.shape
    native, Tp, ng, block_tiles, ilp = _to_native_layout(
        words, block_tiles, ilp
    )
    firsts = gear_window_first_native(
        native, avg_bits, thin_bits, block_tiles, interpret, ilp
    )
    nwpt = firsts.shape[0]
    # (nwpt, 8, Tp/8) -> (8, Tp/8, nwpt) -> flat (t, w) stream order
    out = jnp.transpose(firsts, (1, 2, 0)).reshape(Tp * nwpt)
    return out[: T * nwpt].astype(jnp.int32)


gear_window_first_pallas = _jit_site("ops.rabin_pallas.window_first", gear_window_first_pallas)


def _kernel_first(wref, oref, sth_ref, stl_ref, *, avg_bits: int, ilp: int):
    """First-hit-per-group variant of :func:`_kernel`: emits one u32 per
    GROUP (the group-local offset of the first candidate, or NO_HIT)
    instead of GROUP/PACK packed mask words — 1/8 the output traffic.
    Same ILP interleave; see :func:`.rabin.gear_first_tiled` for the
    semantics."""
    j = pl.program_id(1)
    mask = U32((1 << avg_bits) - 1)
    btl = sth_ref.shape[-1] // ilp
    sent = U32(NO_HIT)

    @pl.when(j == 0)
    def _init():
        sth_ref[0] = jnp.zeros(sth_ref.shape[1:], U32)
        stl_ref[0] = jnp.zeros(stl_ref.shape[1:], U32)

    def chunk(a, k):
        return a[:, k * btl : (k + 1) * btl]

    hh = [chunk(sth_ref[0], k) for k in range(ilp)]
    hl = [chunk(stl_ref[0], k) for k in range(ilp)]
    first = [jnp.full(hh[0].shape, sent, U32) for _ in range(ilp)]
    pos = 0
    for w in range(GROUP // 4):
        word = wref[0, w]
        for s in range(4):
            for k in range(ilp):
                byte = (chunk(word, k) >> U32(8 * s)) & U32(0xFF)
                hh[k], hl[k] = _gear_step(hh[k], hl[k], byte)
                hit = (hh[k] & mask) == U32(0)
                first[k] = jnp.where(
                    hit & (first[k] == sent), U32(pos), first[k]
                )
            pos += 1
    oref[0] = jnp.concatenate(first, axis=-1)
    sth_ref[0] = jnp.concatenate(hh, axis=-1)
    stl_ref[0] = jnp.concatenate(hl, axis=-1)


@functools.partial(
    jax.jit, static_argnames=("avg_bits", "block_tiles", "interpret", "ilp")
)
def gear_first_native(words, avg_bits: int = 13, block_tiles: int = 8192,
                      interpret: bool = False, ilp: int = 8):
    """``words``: (ngroups, GROUP/4, 8, T/8) uint32 -> first-hit offsets
    ``(ngroups, 8, T/8)`` uint32 (NO_HIT = none)."""
    ng, gw, s, tl = words.shape
    if gw != GROUP // 4 or s != _SUBLANE:
        raise ValueError(f"expected (ng, {GROUP // 4}, 8, T/8); got {words.shape}")
    if block_tiles % (_SUBLANE * _LANE):
        raise ValueError(f"block_tiles must be a multiple of {_SUBLANE * _LANE}")
    btl = block_tiles // _SUBLANE
    if tl % btl:
        raise ValueError(f"T/8={tl} not a multiple of tile width {btl}")
    if btl % ilp or (btl // ilp) % _LANE:
        raise ValueError(
            f"block_tiles/8={btl} must split into {ilp} lane-multiples"
        )
    grid = (tl // btl, ng)
    kernel = functools.partial(_kernel_first, avg_bits=avg_bits, ilp=ilp)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, gw, _SUBLANE, btl), lambda i, j: (j, 0, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, _SUBLANE, btl), lambda i, j: (j, 0, i)),
        out_shape=jax.ShapeDtypeStruct((ng, _SUBLANE, tl), jnp.uint32),
        scratch_shapes=[
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
            pltpu.VMEM((1, _SUBLANE, btl), jnp.uint32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the name the device trace shows this kernel under
        name="gear_first",
    )(words)


@functools.partial(
    jax.jit, static_argnames=("avg_bits", "block_tiles", "interpret", "ilp")
)
def gear_first_pallas(words, avg_bits: int = 13,
                      block_tiles: int | None = None,
                      interpret: bool = False, ilp: int | None = None):
    """Drop-in for :func:`.rabin.gear_first_tiled`: (T, S/4) uint32 tiles
    in, (T, S/GROUP) first-hit offsets out, Pallas-accelerated."""
    T, _ = words.shape
    native, Tp, ng, block_tiles, ilp = _to_native_layout(
        words, block_tiles, ilp
    )
    firsts = gear_first_native(native, avg_bits, block_tiles, interpret, ilp)
    out = jnp.transpose(firsts.reshape(ng, Tp), (1, 0))
    return out[:T]


gear_first_pallas = _jit_site("ops.rabin_pallas.first", gear_first_pallas)


@functools.partial(
    jax.jit, static_argnames=("avg_bits", "block_tiles", "interpret", "ilp")
)
def gear_candidates_pallas(words, avg_bits: int = 13,
                           block_tiles: int | None = None,
                           interpret: bool = False, ilp: int | None = None):
    """Drop-in for :func:`.rabin.gear_candidates_tiled`: (T, S/4) uint32
    tiles in, (T, S/PACK) packed bitmask out, Pallas-accelerated.

    Pads the tile count up to ``block_tiles`` (zero tiles are discarded
    on output).  Defaults pick the measured sweet spot — 8192-tile blocks
    with 8 interleaved chains: 13.8-14.1 GiB/s kernel-only on v5e-1 at
    the 1 GiB/128 KiB-tile bench shape (round-3 driver runs; 2x the
    un-interleaved kernel; ilp=16 with 16k-tile blocks and a 32-bit-state
    gear variant both measured within noise of this, so the kernel is not
    ALU- or ILP-bound at this rate) — scaled down for small batches so
    padding never exceeds one power-of-two step.
    """
    T, _ = words.shape
    native, Tp, ng, block_tiles, ilp = _to_native_layout(
        words, block_tiles, ilp
    )
    bits = gear_candidates_native(native, avg_bits, block_tiles, interpret, ilp)
    # (ng, GROUP/PACK, 8, Tp/8) -> (T, S/PACK)
    out = jnp.transpose(
        bits.reshape(ng, GROUP // PACK, Tp), (2, 0, 1)
    ).reshape(Tp, ng * GROUP // PACK)
    return out[:T]


gear_candidates_pallas = _jit_site("ops.rabin_pallas.candidates", gear_candidates_pallas)
