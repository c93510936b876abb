"""Content-defined chunking: gear rolling hash over tiled streams.

The reference streams blobs in O(chunk) memory but never content-chunks
them (chunking lives above the wire protocol in dat core; reference:
README.md:73 "blobs are streamed, never buffered").  The TPU framework
adds content-defined chunking as a device kernel per BASELINE.json
config 4 ("Rabin rolling-hash content-defined chunking over 10 GiB
blob").

Algorithm (designed for SPMD, not translated from anything):

* **Gear-style rolling hash** ``h_{i} = (h_{i-1} << 1) + g(b_i)`` over a
  64-bit state carried as (hi, lo) uint32 lane pairs.  A byte's
  contribution is shifted out after 64 positions, so the hash at any
  position depends only on the trailing 64-byte window — which makes the
  stream *tileable*: tiles recompute a 64-byte overlap instead of
  serializing (SURVEY.md §7 hard part (b)).
* The stream is defined to be **seeded with WINDOW zero bytes**: position
  0's hash state is the state after processing 64 zero bytes.  This makes
  every tile identical in shape — each one carries a 64-byte prefix (the
  preceding stream bytes, or the zero seed at the stream head) — so tile
  construction is a uniform vectorized layout op with no first-tile
  special case.
* ``g(b) = ((b+1) * C1, (b+1) * C2)`` — a table-free multiplicative
  scramble (two 32-bit odd constants), chosen over the classic 256-entry
  gear table because TPU vector lanes have no cheap gather; two u32
  multiplies replace a table lookup.
* A position is a **candidate boundary** when the top hash word masked by
  ``(1 << avg_bits) - 1`` is zero → average chunk size 2**avg_bits.
* The kernel scans byte groups (outer `lax.scan`, inner unrolled; the
  Pallas variant in :mod:`.rabin_pallas` for TPU) over all tiles in
  parallel and emits **packed bitmasks** (1 bit per byte).  Candidate
  *positions* are then extracted **on device** with a two-level sparse
  pass (nonzero packed words -> nonzero bits), so the host transfer is
  O(candidates) — ~4 bytes per ~2**avg_bits input bytes — instead of the
  dense 1-bit-per-byte mask: D2H bandwidth is orders of magnitude
  below HBM's.
* Min/max chunk-size constraints are applied by a greedy pass over the
  sparse candidates (sequential by nature): the native C loop in
  ``native/dat_native.cpp`` when available, else the Python fallback.

Memory discipline: tiles stream through the device; a 10 GiB blob is
processed in bounded slabs (`chunk_stream`), never resident at once —
the device-scale analogue of the reference's O(chunk) streaming.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.trace import span
from .u64 import U32
from ..obs.device import jit_site as _jit_site
from ..obs.device import note_engine as _note_engine
from ..obs.metrics import OBS as _OBS
from ..obs.metrics import counter as _counter

# fused1p extractions refused by the on-chip cross-check (each one
# recomputes on the bitmask route; OBSERVABILITY.md single-pass catalog)
_M_FUSED_REFUSED = _counter("cdc.fused.crosscheck.refused")

WINDOW = 64  # bytes: contributions shift out of the 64-bit state after this
# golden-ratio odd constants — datlint's wire-constant-parity rule
# cross-checks these against both native scan loops (a fork silently
# forks the cut sequence between routes)
_GEAR_C1 = 0x9E3779B1
_GEAR_C2 = 0x85EBCA77
_C1 = np.uint32(_GEAR_C1)
_C2 = np.uint32(_GEAR_C2)

PACK = 32  # bit positions per packed uint32 output word
GROUP = 256  # bytes per outer scan step: large enough that per-step scan
# overhead (xs slicing, carry threading — ~30us/step through XLA) is
# amortized against the ~12 ops/byte of hash work

# Per-tile prefix bytes: one whole GROUP.  Only the last WINDOW bytes of
# it are real context (the hash forgets everything older); padding the
# prefix to a full GROUP makes every tile's valid byte range start on a
# group boundary, so the first-hit-per-group kernel output maps to
# aligned absolute windows with no cross-group straddling.
_PREFIX = GROUP
_PREFIX_WORDS = _PREFIX // 4


def _gear_step(hh, hl, byte_u32):
    """One rolling-hash update on (T,) lanes; returns new (hh, hl)."""
    v = byte_u32 + U32(1)
    gl = v * _C1
    gh = v * _C2
    # h = (h << 1) + g  (64-bit via lane pairs)
    sh = (hh << U32(1)) | (hl >> U32(31))
    sl = hl << U32(1)
    lo = sl + gl
    carry = (lo < sl).astype(U32)
    hi = sh + gh + carry
    return hi, lo


@functools.partial(jax.jit, static_argnames=("avg_bits",))
def gear_candidates_tiled(words, avg_bits: int = 13):
    """Candidate-boundary bitmask for tiled byte streams.

    ``words``: (T, S/4) uint32 — T tiles of S bytes, little-endian packed
    (byte j of a tile is ``(words[t, j//4] >> (8*(j%4))) & 0xFF``).  The
    hash state is seeded from zero at each tile start; the caller
    arranges tiles so each one carries its preceding ``WINDOW`` stream
    bytes (or the zero seed) as a prefix, and drops the prefix bits.

    Returns ``bits``: (T, S/PACK) uint32 — bit ``j%32`` of word ``j//32``
    set iff position j is a candidate (hash top word & mask == 0).
    """
    T, nwords = words.shape
    if (nwords * 4) % GROUP:
        raise ValueError(f"tile bytes must be a multiple of {GROUP}")
    mask = U32((1 << avg_bits) - 1)

    groups = words.reshape(T, (nwords * 4) // GROUP, GROUP // 4)
    groups = jnp.transpose(groups, (1, 0, 2))  # (ngroups, T, GROUP/4)

    def group_step(carry, grp):
        hh, hl = carry
        packed = []
        acc = jnp.zeros((T,), dtype=U32)
        bit = 0
        for w in range(GROUP // 4):
            word = grp[:, w]
            for s in range(4):
                byte = (word >> U32(8 * s)) & U32(0xFF)
                hh, hl = _gear_step(hh, hl, byte)
                hit = (hh & mask) == U32(0)
                acc = acc | (hit.astype(U32) << U32(bit))
                bit += 1
                if bit == PACK:
                    packed.append(acc)
                    acc = jnp.zeros((T,), dtype=U32)
                    bit = 0
        return (hh, hl), jnp.stack(packed, axis=1)  # (T, GROUP/PACK)

    h0 = (jnp.zeros((T,), U32), jnp.zeros((T,), U32))
    _, bits = jax.lax.scan(group_step, h0, groups)  # (ngroups, T, GROUP/PACK)
    return jnp.transpose(bits, (1, 0, 2)).reshape(T, -1)


gear_candidates_tiled = _jit_site("ops.rabin.candidates_tiled", gear_candidates_tiled)


NO_HIT = GROUP  # first-hit sentinel: no candidate in this group


@functools.partial(jax.jit, static_argnames=("avg_bits",))
def gear_first_tiled(words, avg_bits: int = 13):
    """First candidate offset per GROUP-byte group (portable XLA path).

    Same scan as :func:`gear_candidates_tiled` but each group emits one
    uint32 — the group-local offset of its *first* candidate, or
    :data:`NO_HIT` — instead of GROUP/PACK packed mask words.  This is
    the thinned-extraction kernel: 1/8 the output volume of the bitmask
    and a GROUP-granular head start on window thinning, at the cost of
    only seeing one candidate per group (callers thin at windows >= one
    GROUP, where that is exactly the information they keep anyway).

    Returns (T, S/GROUP) uint32.
    """
    T, nwords = words.shape
    if (nwords * 4) % GROUP:
        raise ValueError(f"tile bytes must be a multiple of {GROUP}")
    mask = U32((1 << avg_bits) - 1)

    groups = words.reshape(T, (nwords * 4) // GROUP, GROUP // 4)
    groups = jnp.transpose(groups, (1, 0, 2))  # (ngroups, T, GROUP/4)
    sent = U32(NO_HIT)

    def group_step(carry, grp):
        hh, hl = carry
        first = jnp.full((T,), sent, U32)
        pos = 0
        for w in range(GROUP // 4):
            word = grp[:, w]
            for s in range(4):
                byte = (word >> U32(8 * s)) & U32(0xFF)
                hh, hl = _gear_step(hh, hl, byte)
                hit = (hh & mask) == U32(0)
                first = jnp.where(hit & (first == sent), U32(pos), first)
                pos += 1
        return (hh, hl), first  # (T,)

    h0 = (jnp.zeros((T,), U32), jnp.zeros((T,), U32))
    _, firsts = jax.lax.scan(group_step, h0, groups)  # (ngroups, T)
    return jnp.transpose(firsts, (1, 0))


gear_first_tiled = _jit_site("ops.rabin.first_tiled", gear_first_tiled)


# ---------------------------------------------------------------------------
# device-resident candidate extraction
# ---------------------------------------------------------------------------


def _first_bit_per_window(wins):
    """First set-bit offset per window row of packed uint32 words, or
    ``1 << 30`` for empty windows — the ONE owner of the windowed
    first-candidate reduction (the thinning fast path and the exact
    extractor's small-window mode both ride it)."""
    wnz = wins != U32(0)
    first_w = jnp.argmax(wnz, axis=1).astype(jnp.int32)
    wval = jnp.take_along_axis(wins, first_w[:, None], axis=1)[:, 0]
    lsb = wval & (U32(0) - wval)
    bitpos = _popcount32(lsb - U32(1)).astype(jnp.int32)
    return jnp.where(jnp.any(wnz, axis=1), first_w * PACK + bitpos, 1 << 30)


def _build_rows(words_padded, pre_row, T: int, stride: int):
    """[context GROUP | payload] rows, (T, _PREFIX_WORDS + stride/4).

    Row t covers stream bytes [t*stride - _PREFIX, (t+1)*stride): one
    whole warm-up GROUP (its last WINDOW bytes are the real preceding
    context — earlier bytes are don't-cares the hash forgets; the stream
    head gets the zero seed) followed by the payload.  The valid byte
    range of every row is [_PREFIX, _PREFIX + stride) — absolute stream
    position ``t*stride + j - _PREFIX`` — which starts on a GROUP
    boundary, so group-granular kernel outputs map onto aligned absolute
    windows.  Pure layout ops on device: no flat prefixed copy of the
    whole buffer is materialized.
    """
    sw = stride // 4
    payload = words_padded.reshape(T, sw)
    ctx = jnp.concatenate(
        [pre_row[None, :], payload[:-1, -_PREFIX_WORDS:]], axis=0
    )
    return jnp.concatenate([ctx, payload], axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("T", "stride", "avg_bits", "cap2", "use_pallas",
                     "thin_bits", "route"),
)
def _extract_first_occ(words_padded, pre_row, T: int, stride: int,
                       avg_bits: int, cap2: int, use_pallas: bool,
                       thin_bits: int = 11, route: str = "bitmask"):
    """Thinned candidate extraction: occupancy bitmap + in-window offsets.

    **Candidate thinning**: at most the *first* candidate in each aligned
    ``2**thin_bits``-byte window survives.  Chunking callers pass
    ``thin_bits = log2(min_size)``: two candidates closer than min_size
    can never both become cuts, so thinning only shifts the occasional
    cut to an equivalent in-window neighbor.  Deterministic for a given
    stream; documented policy, not an approximation knob.

    Three equivalent kernel routes (``route``; all produce identical
    candidate sets — tested):

    * ``"bitmask"`` (default) — the BITMASK kernel + a vectorized
      first-set-bit reduction per window.  The first-hit kernel's
      per-byte ``where`` chain lengthens the gear loop's serial
      dependency (the scan's actual binder), while the bitmask kernel's
      ``or``-accumulate does not — the reduction over packed words is
      ~1 op per 32 bytes, off the critical path.  8x the kernel OUTPUT
      volume, but that output never leaves the device.
    * ``"first"`` — the first-hit-per-GROUP kernel + a min over groups
      (1/8 the kernel output volume; kept for measurement comparison —
      DAT_CDC_FIRST_KERNEL=1 / DAT_CDC_ROUTE=first).
    * ``"fused"`` — the window-first reduction fused INTO the gear
      kernel (per-packed-word tracking in registers, one u32 flushed
      per window): no 1-bit/byte mask ever lands in HBM and no second
      reduction dispatch runs.  Pallas-only; falls back to "bitmask"
      off-TPU.  DAT_CDC_ROUTE=fused.

    The host result rides in two dense-free pieces —

    * ``occ``: (ceil(nwin/32),) uint32 — bit w set iff window w holds a
      candidate (fixed 1 bit per window: 64 KiB/GiB at 2 KiB windows);
    * ``offs``: (cap2,) uint16 — the in-window byte offset of each
      occupied window's candidate, compacted in window order —

    so the transfer is O(windows)/8 + O(candidates)*2 bytes with **no
    device->host count round-trip**: the host derives the candidate
    count (and the cap2-overflow check) from popcounting ``occ``.
    """
    rows = _build_rows(words_padded, pre_row, T, stride)
    if route in ("fused", "fused1p") and not use_pallas:
        route = "bitmask"  # the fused kernels have no XLA formulation
    viol = None
    if route == "fused1p":
        # single-pass route: the window-first kernel with the on-chip
        # occupancy cross-check; ``viol`` rides out so candidates_begin
        # can REFUSE divergent cuts and recompute on the bitmask route
        from .fused_cdc_hash_pallas import gear_window_first_checked

        first, viol = gear_window_first_checked(rows, avg_bits, thin_bits)
    elif route == "fused":
        from .rabin_pallas import gear_window_first_pallas

        first = gear_window_first_pallas(rows, avg_bits, thin_bits)
    elif route == "first":
        if use_pallas:
            from .rabin_pallas import gear_first_pallas

            firsts = gear_first_pallas(rows, avg_bits)
        else:
            firsts = gear_first_tiled(rows, avg_bits)
        vg = firsts[:, 1:]  # drop warm-up group 0; (T, stride/GROUP)
        flatg = vg.reshape(-1).astype(jnp.int32)
        gpw = (1 << thin_bits) // GROUP  # groups per window
        wins = flatg.reshape(-1, gpw)
        gidx = jnp.arange(gpw, dtype=jnp.int32) * GROUP
        hitpos = jnp.where(wins < NO_HIT, wins + gidx[None, :], 1 << 30)
        first = jnp.min(hitpos, axis=1)  # in-window first-candidate offset
    else:
        if use_pallas:
            from .rabin_pallas import gear_candidates_pallas

            bits = gear_candidates_pallas(rows, avg_bits)
        else:
            bits = gear_candidates_tiled(rows, avg_bits)
        vw = bits[:, _PREFIX // PACK : _PREFIX // PACK + stride // PACK]
        wpw = (1 << thin_bits) // PACK  # packed words per window
        first = _first_bit_per_window(vw.reshape(-1, wpw))
    nwin = first.shape[0]
    has = first < (1 << 30)
    hasp = has
    if nwin % 32:
        hasp = jnp.pad(has, (0, 32 - nwin % 32))
    occ = jnp.sum(
        hasp.reshape(-1, 32).astype(U32)
        << jnp.arange(32, dtype=U32)[None, :],
        axis=1,
    )
    (widx,) = jnp.nonzero(has, size=cap2, fill_value=0)
    offs = first[widx].astype(jnp.uint16)
    if viol is not None:  # fused1p: the cross-check flag rides along
        return occ, offs, viol
    return occ, offs


_extract_first_occ = _jit_site("ops.rabin.extract_first_occ", _extract_first_occ)


@functools.partial(
    jax.jit,
    static_argnames=("T", "stride", "avg_bits", "cap", "cap2", "use_pallas",
                     "thin_bits"),
)
def _extract_candidates(words_padded, pre_row, T: int, stride: int,
                        avg_bits: int, cap: int, cap2: int,
                        use_pallas: bool, thin_bits: int | None = None):
    """Tile + scan + sparse-extract, all on device (see :func:`_build_rows`
    for the layout).

    Sparse extraction keeps the D2H volume O(candidates) — ~4 bytes per
    2**avg_bits input bytes instead of the dense 1-bit-per-byte mask.

    Two modes:

    * ``thin_bits=None`` — exact: every candidate position, via two-level
      nonzero (words, then bits).  The full-width ``jnp.nonzero`` lowers
      to a scatter over the whole word mask (~0.3 s/GiB measured on
      v5e-1), so this mode is for correctness tests and modest inputs.
      (The fast path for chunking is :func:`_extract_first_occ`.)
    * ``thin_bits=k`` (< 8) — small-window thinning over the packed
      bitmask: argmax per window + a small nonzero.

    Returns ``(positions, ncand, nover)``: ``positions`` (cap2,) int32
    absolute byte positions (first ``ncand`` entries valid, ascending);
    ``nover`` > cap means overflow — retry with a larger cap.
    """
    rows = _build_rows(words_padded, pre_row, T, stride)

    if use_pallas:
        from .rabin_pallas import gear_candidates_pallas

        bits = gear_candidates_pallas(rows, avg_bits)
    else:
        bits = gear_candidates_tiled(rows, avg_bits)

    # valid packed words: everything after the warm-up prefix's bit-words
    # [0, _PREFIX/PACK)
    vw = bits[:, _PREFIX // PACK : _PREFIX // PACK + stride // PACK]
    flat = vw.reshape(-1)

    if thin_bits is not None:
        W = 1 << thin_bits  # window bytes; PACK-aligned power of two
        wins = flat.reshape(-1, W // PACK)  # (nwin, wpw)
        inwin = _first_bit_per_window(wins)
        has = inwin < (1 << 30)
        nwin = wins.shape[0]
        pos = jnp.arange(nwin, dtype=jnp.int32) * W + inwin
        ncand = jnp.sum(has.astype(jnp.int32))
        (widx,) = jnp.nonzero(has, size=cap2, fill_value=0)
        return pos[widx], ncand, ncand

    nz = flat != U32(0)
    nword = jnp.sum(nz.astype(jnp.int32))
    (widx,) = jnp.nonzero(nz, size=cap, fill_value=0)
    wvals = flat[widx]
    # level 2: expand selected words into absolute byte positions
    wpt = stride // PACK  # valid words per tile
    t = widx // wpt
    w = widx % wpt
    base = (t * stride + w * PACK).astype(jnp.int32)
    live = (jnp.arange(cap) < nword)[:, None]
    bitsel = ((wvals[:, None] >> jnp.arange(PACK, dtype=U32)[None, :])
              & U32(1)).astype(bool) & live
    pos = base[:, None] + jnp.arange(PACK, dtype=jnp.int32)[None, :]
    ncand = jnp.sum(bitsel.astype(jnp.int32))
    (pidx,) = jnp.nonzero(bitsel.reshape(-1), size=cap2, fill_value=0)
    positions = pos.reshape(-1)[pidx]
    return positions, ncand, nword


_extract_candidates = _jit_site("ops.rabin.extract_candidates", _extract_candidates)


def _popcount32(x):
    """Bit population count on uint32 lanes (SWAR, 12 elementwise ops)."""
    x = x - ((x >> U32(1)) & U32(0x55555555))
    x = (x & U32(0x33333333)) + ((x >> U32(2)) & U32(0x33333333))
    x = (x + (x >> U32(4))) & U32(0x0F0F0F0F)
    return (x * U32(0x01010101)) >> U32(24)


def _clamp_thin_bits(thin_bits: int | None, stride: int) -> int | None:
    """One owner of the thinning-policy clamps: the host scan and the
    device tiles must produce IDENTICAL candidate sets, so both routes
    apply exactly these rules.  None = no thinning.

    * windows below 32 bytes can't cover a packed word: no thinning;
    * the window must divide the tile (stride's largest power-of-two
      divisor) and fit the u16 in-window offset range (<= 16).
    """
    if thin_bits is None or thin_bits < 5:
        return None
    tz = (stride & -stride).bit_length() - 1
    thin_bits = min(thin_bits, tz, 16)
    return thin_bits if thin_bits >= 5 else None


def pallas_active() -> bool:
    """The ONE owner of the "do Pallas kernels run here" decision —
    candidates_begin's route dispatch and effective_route's
    fused->bitmask aliasing both consult this, so they can never
    disagree about which kernel actually executes."""
    return jax.default_backend() == "tpu"


def effective_route(use_pallas: bool | None = None) -> str:
    """The ONE owner of extraction-route resolution: consult
    ``DAT_CDC_ROUTE`` (values ``bitmask``/``first``/``fused``/
    ``fused1p``), fall back to the legacy ``DAT_CDC_FIRST_KERNEL`` knob,
    and alias ``fused``/``fused1p`` to ``bitmask`` off-Pallas (neither
    fused kernel has an XLA formulation; fused1p's HOST engine is routed
    separately by :func:`..runtime.content.content_digests`, which
    consults the raw env value).  The dispatch path and
    ``chip_smoke.py``'s route check both use this, so the route named
    is always the route that actually ran.  ``use_pallas=None`` consults
    :func:`pallas_active`."""
    import os

    route = os.environ.get("DAT_CDC_ROUTE")
    if route not in ("bitmask", "first", "fused", "fused1p"):
        route = ("first" if os.environ.get("DAT_CDC_FIRST_KERNEL") == "1"
                 else "bitmask")
    if use_pallas is None:
        use_pallas = pallas_active()
    if route in ("fused", "fused1p") and not use_pallas:
        route = "bitmask"
    return route


def _start_d2h(arrays) -> None:
    """Start D2H transfers for the extraction outputs now, concurrently:
    by collect() time they are local (or in flight under the next slab's
    compute) instead of serializing inside collect, on the fast path's
    critical path."""
    for arr in arrays:
        arr.copy_to_host_async()


def candidates_begin(words, nbytes: int, avg_bits: int = 13,
                     tile_bytes: int = 1 << 17,
                     prefix: np.ndarray | None = None,
                     thin_bits: int | None = None):
    """Candidate positions for a (device- or host-resident) word buffer.

    ``words``: flat uint32 array (jax or numpy), little-endian packed
    stream bytes; ``nbytes``: true stream length (trailing bytes of the
    last word beyond it must be zero).  ``prefix``: the WINDOW bytes
    preceding this buffer in the stream as 16 uint32 words (None = the
    zero seed, i.e. this buffer is the stream head).  ``thin_bits``: keep
    at most the first candidate per aligned ``2**thin_bits``-byte window
    (see :func:`_extract_candidates`; chunkers pass log2(min_size)).
    Returns sorted absolute candidate positions (int64, < nbytes) on the
    host.

    This is the device-resident fast path: when ``words`` already lives
    in HBM, the only host traffic is the O(candidates) position list.

    Returns a zero-arg ``collect()`` closure: the device scan is
    dispatched asynchronously here, and ``collect()`` blocks on the
    result transfer — so a caller streaming multiple slabs can overlap
    slab N's D2H with slab N+1's compute (:func:`chunk_stream` does, at
    depth 2).
    """
    if nbytes == 0:
        return lambda: np.empty((0,), dtype=np.int64)
    if nbytes > 1 << 31:
        raise ValueError("per-call limit is 2 GiB; slab your stream")
    if tile_bytes % GROUP:
        raise ValueError(f"tile_bytes must be a multiple of {GROUP}")
    stride = tile_bytes
    T = -(-nbytes // stride)
    sw = stride // 4
    words = jnp.asarray(words).reshape(-1)
    if words.shape[0] != -(-nbytes // 4):
        raise ValueError(
            f"word buffer holds {words.shape[0] * 4} bytes; nbytes={nbytes} "
            f"needs exactly {-(-nbytes // 4)} words (zero-pad the tail)"
        )
    # prefix is the WINDOW real context bytes; the GROUP-wide row prefix
    # is zero-filled in front of them (don't-care bytes, see _build_rows)
    pre = jnp.zeros((_PREFIX_WORDS,), U32)
    if prefix is not None:
        ctx = jnp.asarray(prefix, dtype=U32).reshape(-1)
        if ctx.shape[0] != WINDOW // 4:
            raise ValueError(f"prefix must be {WINDOW} bytes")
        pre = pre.at[-(WINDOW // 4):].set(ctx)
    pad = T * sw - words.shape[0]
    if pad > 0:
        words = jnp.concatenate([words, jnp.zeros((pad,), U32)])

    thin_bits = _clamp_thin_bits(thin_bits, stride)

    use_pallas = pallas_active()
    # expected candidates ~= nbytes / 2**avg_bits (sparse).  4x margin,
    # then grow geometrically on the (rare) overflow.
    cap0 = max(256, (T * stride) >> max(avg_bits - 2, 0))
    if thin_bits is not None:
        cap0 = min(cap0, (T * stride) >> thin_bits)

    if thin_bits is not None and thin_bits >= 8:
        # fast path: windowed first-candidate extraction + occ/offsets
        # transfer (kernel route per _extract_first_occ; the env knobs
        # are for on-device measurement comparison)
        route = effective_route(use_pallas)
        with span("cdc.dispatch"):
            first = _extract_first_occ(
                words, pre, T, stride, avg_bits, cap0, use_pallas,
                thin_bits, route=route,
            )
            _start_d2h(first)

        def checked(ext, rt, cap):
            """Refuse a fused1p extraction whose on-chip cross-check
            tripped (the two independent in-kernel reductions disagree)
            and recompute AT THE SAME CAP on the bitmask route — EVERY
            extraction consults this, the cap-growth retries included
            (each retry is a different compiled program instance, so a
            clean first pass proves nothing about them)."""
            if len(ext) == 3 and int(ext[2]) != 0:
                if _OBS.on:
                    _M_FUSED_REFUSED.inc()
                rt = "bitmask"
                ext = _extract_first_occ(
                    words, pre, T, stride, avg_bits, cap, use_pallas,
                    thin_bits, route=rt,
                )
            return ext, rt

        def collect() -> np.ndarray:
            with span("cdc.collect"):
                from .merkle import unpack_mask

                ext, rt = checked(first, route, cap0)
                occ, offs = ext[0], ext[1]
                winidx = np.nonzero(
                    unpack_mask(occ, T * stride >> thin_bits)
                )[0]
                cap = cap0
                while len(winidx) > cap:
                    cap *= 4
                    ext, rt = checked(_extract_first_occ(
                        words, pre, T, stride, avg_bits, cap, use_pallas,
                        thin_bits, route=rt,
                    ), rt, cap)
                    offs = ext[1]
                offs_np = np.asarray(offs)
                out = (winidx << thin_bits) + offs_np[: len(winidx)].astype(
                    np.int64
                )
                return out[out < nbytes]

        return collect

    with span("cdc.dispatch"):
        first = _extract_candidates(
            words, pre, T, stride, avg_bits, cap0, cap0, use_pallas,
            thin_bits,
        )
        _start_d2h(first)

    def collect() -> np.ndarray:
        with span("cdc.collect"):
            positions, ncand, nover = first
            cap = cap0
            while int(nover) > cap or int(ncand) > cap:
                cap *= 4
                positions, ncand, nover = _extract_candidates(
                    words, pre, T, stride, avg_bits, cap, cap, use_pallas,
                    thin_bits,
                )
            out = np.asarray(positions[: int(ncand)], dtype=np.int64)
            return out[out < nbytes]

    return collect


def candidates_words(words, nbytes: int, avg_bits: int = 13,
                     tile_bytes: int = 1 << 17,
                     prefix: np.ndarray | None = None,
                     thin_bits: int | None = None) -> np.ndarray:
    """Synchronous :func:`candidates_begin`: positions, sorted, < nbytes."""
    return candidates_begin(
        words, nbytes, avg_bits, tile_bytes, prefix, thin_bits
    )()


# ---------------------------------------------------------------------------
# host edge
# ---------------------------------------------------------------------------


def _greedy_select_py(candidates: np.ndarray, length: int, min_size: int,
                      max_size: int) -> list[int]:
    """Pure-Python min/max pass (fallback when the native lib is absent)."""
    out: list[int] = []
    start = 0
    i = 0
    n = len(candidates)
    while length - start > max_size:
        lo = start + min_size
        hi = start + max_size
        while i < n and candidates[i] < lo:
            i += 1
        if i < n and candidates[i] <= hi:
            cut = int(candidates[i])
            i += 1
        else:
            cut = hi
        out.append(cut)
        start = cut
    out.append(length)
    return out


def _greedy_select(candidates: np.ndarray, length: int, min_size: int,
                   max_size: int) -> list[int]:
    """Sequential min/max pass over sorted candidate byte offsets.

    Returns chunk end-offsets (exclusive), always ending with ``length``.
    A cut is taken at the first candidate >= min_size after the previous
    cut; if none lands before max_size, a forced cut at max_size.

    The pass is inherently sequential (each cut shifts the min/max
    horizon), so it runs as a native C loop
    (``native/dat_native.cpp:dat_greedy_select``) — at ~10ns/cut it is
    invisible next to the device scan; the Python loop fallback costs
    ~1us/cut, which at 1M cuts would dominate the whole pipeline.
    """
    from ..runtime import native

    lib = native.get_lib()
    if lib is None:
        return _greedy_select_py(candidates, length, min_size, max_size)
    with span("cdc.greedy"):
        cands = np.ascontiguousarray(candidates, dtype=np.int64)
        cap = length // max(min_size, 1) + 2
        out = np.empty(cap, dtype=np.int64)
        n = lib.dat_greedy_select(
            cands, len(cands), length, min_size, max_size, out, cap
        )
    if n < 0:  # capacity can't trip given the bound above; be safe anyway
        return _greedy_select_py(candidates, length, min_size, max_size)
    return out[:n].tolist()


def host_candidates(data: bytes, avg_bits: int = 13) -> list[int]:
    """Pure-Python reference for the device candidate kernel (tests).

    Implements the seeded-stream definition: the hash state at position 0
    is the state after processing WINDOW zero bytes.
    """
    mask = (1 << avg_bits) - 1
    h = 0
    g0 = (1 * int(_C1) & 0xFFFFFFFF) | ((1 * int(_C2) & 0xFFFFFFFF) << 32)
    for _ in range(WINDOW):
        h = ((h << 1) + g0) & 0xFFFFFFFFFFFFFFFF
    out = []
    for j, b in enumerate(data):
        g = ((b + 1) * int(_C1) & 0xFFFFFFFF) | (
            ((b + 1) * int(_C2) & 0xFFFFFFFF) << 32
        )
        h = ((h << 1) + g) & 0xFFFFFFFFFFFFFFFF
        if (h >> 32) & mask == 0:
            out.append(j)
    return out


def chunk_stream(
    data,
    avg_bits: int = 13,
    min_size: int | None = None,
    max_size: int | None = None,
    tile_bytes: int = 1 << 17,
    slab_tiles: int = 8192,
) -> list[int]:
    """Content-defined chunk end-offsets for a byte stream.

    ``data``: bytes or uint8 numpy array.  Processes ``slab_tiles`` tiles
    of ``tile_bytes`` per device dispatch (bounded memory regardless of
    blob size).  The library default slab is 1 GiB: with depth-2
    pipelining TWO slabs are in flight, each holding the input words
    plus the ``_build_rows`` copy (and the bitmask route's mask), so
    HBM high-water is roughly 4x the slab size — 1 GiB slabs fit any
    current backend.  Callers on a >= 16 GiB-HBM device should pass
    ``slab_tiles=16384`` (2 GiB): round-4 phase attribution measured ~63 ms fixed per-dispatch cost against
    ~5 ms/GiB marginal, so fewer, larger slabs win until memory does.
    Host-resident data pays one H2D transfer per slab; for data
    already on device use :func:`candidates_words` +
    :func:`_greedy_select` directly.
    """
    if min_size is None:
        min_size = 1 << (avg_bits - 2)
    if max_size is None:
        max_size = 1 << (avg_bits + 2)
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)
    ) else np.asarray(data, dtype=np.uint8)
    length = len(buf)
    if length == 0:
        return []

    thin_bits = max(min_size, 1).bit_length() - 1  # floor log2: W <= min_size

    # "batch or stay home": on a CPU-only jax the XLA-scan formulation
    # of the gear loop is catastrophically slow (~0.0002 GiB/s e2e
    # measured), while the native C table-driven scan does ~1.2 GiB/s
    # per core — same seeded-stream definition, identical candidates
    # (tested).  DAT_DEVICE_CDC=1/0 overrides.
    from ..utils.routing import prefer_host

    if prefer_host("DAT_DEVICE_CDC"):
        from ..runtime import native

        # the SAME thinning clamps as the device tiles (one owner:
        # _clamp_thin_bits) so host and device produce identical
        # candidate sets and therefore identical cuts for any tile_bytes
        clamped = _clamp_thin_bits(thin_bits, tile_bytes)
        cands = native.gear_candidates(
            buf, avg_bits, -1 if clamped is None else clamped
        )
        if cands is not None:
            if _OBS.on:
                _note_engine("cdc.chunk", "native-host", bytes=length)
            return _greedy_select(cands, length, min_size, max_size)

    if _OBS.on:
        _note_engine("cdc.chunk", effective_route(), bytes=length)
    candidates = _device_candidates(
        buf, avg_bits, tile_bytes, slab_tiles, thin_bits
    )
    return _greedy_select(candidates, length, min_size, max_size)


def host_thin(candidates, thin_bits: int) -> list[int]:
    """First-candidate-per-aligned-window thinning (host reference)."""
    out: list[int] = []
    last_win = -1
    for p in candidates:
        win = p >> thin_bits
        if win != last_win:
            out.append(int(p))
            last_win = win
    return out


def _device_candidates(buf: np.ndarray, avg_bits: int, tile_bytes: int,
                       slab_tiles: int,
                       thin_bits: int | None = None) -> np.ndarray:
    """All candidate positions (sorted, absolute) via tiled device scans.

    One vectorized host copy per slab (into a zero-padded word-aligned
    staging array) and one H2D transfer; candidate positions come back
    via the sparse on-device extraction, so there is no dense-bitmask
    readback and no per-tile host loop (both killed the round-2
    number).
    """
    length = len(buf)
    slab_bytes = tile_bytes * slab_tiles
    out: list[np.ndarray] = []
    pending: list[tuple] = []  # depth-2: overlap slab N's D2H with N+1's scan

    def drain() -> None:
        collect, base = pending.pop(0)
        out.append(collect() + base)

    for begin in range(0, length, slab_bytes):
        end = min(begin + slab_bytes, length)
        nb = end - begin
        staged = np.zeros(-(-nb // 4), dtype="<u4")
        staged.view(np.uint8)[:nb] = buf[begin:end]
        if begin == 0:
            prefix = None
        else:
            pre = np.zeros(WINDOW, dtype=np.uint8)
            pre[:] = buf[begin - WINDOW : begin]
            prefix = pre.view("<u4")
        pending.append((
            candidates_begin(staged, nb, avg_bits, tile_bytes, prefix,
                             thin_bits),
            begin,
        ))
        if len(pending) >= 2:
            drain()
    while pending:
        drain()
    if not out:
        return np.empty((0,), dtype=np.int64)
    return np.concatenate(out)
