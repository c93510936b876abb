"""Rateless coded-symbol set reconciliation (ISSUE 10, ROADMAP item 2).

The sketch protocol (:mod:`.reconcile`) exchanges an O(nslots) table —
wire cost scales with the *dataset*; the tree-guided refinement
(:mod:`..runtime.tree_sync`) costs O(diff · log n) bytes in log n round
trips.  This module implements the rateless-IBLT idea ("Practical
Rateless Set Reconciliation", PAPERS.md): **coded symbols** whose
communication cost is O(k) for a k-record symmetric difference, with no
prior estimate of k.

* An **element** is a 32-byte record digest (the same BLAKE2b output the
  sketch sums into cells).  Identity is the digest value itself, so the
  mapping below is recomputable from a *recovered* element alone —
  nothing out-of-band.
* Element x participates in an infinite pseudorandom sequence of coded-
  symbol indices: index 0 always, then gaps drawn so the marginal
  participation probability at index i decays as ``1/(1 + i/2)`` (the
  paper's density).  Given participation at i and a uniform draw
  ``u = (r+1)/2**32``, the next index is
  ``i + ceil((i + 1.5) * (2**16/sqrt(r+1) - 1))`` — the inverse-CDF of
  the renewal process (see :class:`IndexCursor`).  The per-element draw
  stream is splitmix64 seeded by the digest's first 8 bytes (LE) — the
  same first-word convention :func:`.reconcile.sketch_table` keys its
  slots by.
* A **coded symbol** is 11 little-endian u32 words:
  ``[count | checksum lo | checksum hi | sum[0..8)]`` — word-wise
  wrapping-u32 sums of the participating elements' rows (count 1,
  64-bit checksum of the digest, the 8 digest words).  Word-wise
  arithmetic (no cross-word carries) is what makes the build a plain
  u32 scatter-add on any backend, byte-identical everywhere.
* **Reconciliation**: A streams its coded-symbol prefix; B subtracts
  its own symbols for the same indices.  The difference describes
  exactly the symmetric difference: a cell with count ±1 whose checksum
  matches its sum is **pure** — the sum IS an element held only by A
  (+1) or only by B (−1).  Peeling subtracts recovered elements from
  their other cells, exposing new pure cells, until every cell is zero
  (decode complete) or no pure cell remains (more symbols needed).
  ~1.35·k symbols suffice for large k (paper, Fig. 6); a false-pure
  cell needs a 64-bit checksum collision.

Engines: the scatter-add build runs as a batched JAX op
(:func:`build_symbols_device` — gather + scatter-add over digest
columns, the device route for feeds whose digests are already columns)
or as the numpy reference (:func:`build_symbols_host`); both produce
byte-identical cells (tested).  Index generation is host-side numpy in
both routes — one owner of the float math, so engine choice can never
fork the mapping.  Peeling is host work (:class:`PeelDecoder`):
vectorized numpy rounds, with the sequential tail riding the same round
loop as it shrinks.

Elements are a SET: callers dedupe digests first (a duplicated record
adds 2 to its cells and can never peel); :func:`dedupe_digests` is the
shared helper.
"""

from __future__ import annotations

import threading

import numpy as np

from ..obs.metrics import OBS as _OBS, counter as _counter
from ..utils.trace import span

DIGEST_BYTES = 32
DIGEST_WORDS = 8
SYMBOL_WORDS = 11  # count + 2 checksum words + 8 sum words
SYMBOL_BYTES = SYMBOL_WORDS * 4

# Weighted (variable-size element) cells — the "Rateless Bloom Filters"
# extension (PAPERS.md) the snapshot bootstrap reconciles CDC chunk
# sets with (ISSUE 12): an element is a (digest, byte length) pair and
# the cell grows one wrapping-u32 LENGTH word, so a recovered element
# carries its size — the joiner learns exactly how many bytes each
# missing chunk is, and the participation density below can be
# recomputed from the recovered value alone (nothing out-of-band, the
# same recoverability invariant as the unweighted construction).
WSYMBOL_WORDS = 12  # count + 2 checksum words + 8 sum words + length
WSYMBOL_BYTES = WSYMBOL_WORDS * 4

# telemetry (OBSERVABILITY.md "reconcile.*"): symbols built (cells
# produced into a local prefix) and elements recovered by peeling
_M_SYMBOLS = _counter("reconcile.symbols")
_M_PEELED = _counter("reconcile.peeled")

# splitmix64 constants — written down independently in the native
# engine (native/dat_native.cpp dat_rateless_build); a fork is a ROUTE
# fork (two engines mapping elements to different coded symbols), so
# the copies are parity-watched by datlint wire-constant-parity exactly
# like GEAR_C1/GEAR_C2.
RATELESS_GAMMA = 0x9E3779B97F4A7C15
RATELESS_MIX1 = 0xBF58476D1CE4E5B9
RATELESS_MIX2 = 0x94D049BB133111EB

# weighted-participation constants (same parity story — the native
# dat_rateless_build_w twin carries `// wire:` markers): an element's
# weight class is ``min(W_CAP, bit_length(len >> W_SHIFT))`` and its
# index gaps divide by ``class + 1``, so a 1 MiB chunk participates in
# ~9x the cells of a 4 KiB one — heavy chunks decode first, which is
# what makes the WANT set's wire cost track BYTES of divergence, not
# just element count ("Rateless Bloom Filters", PAPERS.md).  A fork
# here maps elements to DIFFERENT cells per engine: the GEAR
# route-fork class.
RATELESS_W_SHIFT = 12
RATELESS_W_CAP = 8

_GAMMA = np.uint64(RATELESS_GAMMA)
_MIX1 = np.uint64(RATELESS_MIX1)
_MIX2 = np.uint64(RATELESS_MIX2)

_BUILD_JIT = None  # lazy: keep jax out of module import


def _mix64(z: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer: the one bit-mixing primitive this module
    uses (PRNG draws and checksums both ride it)."""
    z = z.astype(np.uint64, copy=True)
    z ^= z >> np.uint64(30)
    z *= _MIX1
    z ^= z >> np.uint64(27)
    z *= _MIX2
    z ^= z >> np.uint64(31)
    return z


def _digest_words(digests: np.ndarray) -> np.ndarray:
    """(n, 32) u8 digests -> (n, 8) u32 LE words (zero-copy view)."""
    d = np.ascontiguousarray(digests, dtype=np.uint8)
    if d.ndim != 2 or d.shape[1] != DIGEST_BYTES:
        raise ValueError(f"digests must be (n, {DIGEST_BYTES}) bytes")
    return d.view("<u4")


def checksum_words(sum_words: np.ndarray) -> np.ndarray:
    """64-bit checksum of each digest row, as (n, 2) u32 words.

    Computed from the 8 sum words alone, so a peel candidate's checksum
    is recomputable from the recovered value.  Four u64 lanes chained
    through :func:`_mix64` — NOT the identity on the seed word, so a
    corrupted cell whose sum and checksum were perturbed together still
    fails the pure test (the fault-injection arm's flip class).
    """
    w = np.ascontiguousarray(sum_words, dtype=np.uint32)
    lanes = w.view("<u8")  # (n, 4) u64: adjacent word pairs
    acc = _mix64(lanes[:, 0] + _GAMMA)
    for k in range(1, 4):
        acc = _mix64(acc ^ lanes[:, k])
    out = np.empty((len(w), 2), dtype=np.uint32)
    out[:, 0] = (acc & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    out[:, 1] = (acc >> np.uint64(32)).astype(np.uint32)
    return out


def element_rows(digests: np.ndarray) -> np.ndarray:
    """(n, 32) u8 digests -> (n, 11) u32 symbol rows (count=1)."""
    words = _digest_words(digests)
    rows = np.empty((len(words), SYMBOL_WORDS), dtype=np.uint32)
    rows[:, 0] = 1
    rows[:, 1:3] = checksum_words(words)
    rows[:, 3:] = words
    return rows


def dedupe_digests(digests: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Unique digest rows (first-occurrence order) + their source rows.

    Coded symbols reconcile SETS: a digest present twice on one side
    adds 2 to every cell it touches and can never peel.  Returns
    ``(unique (m,32) u8, first_index (m,) int64)``.

    Sorts by the digests' first u64 word (the cheap discriminant — a
    full 32-byte lexicographic unique costs ~20x at feed scale) and
    resolves only the colliding runs against the full rows, so a
    first-word collision between DISTINCT digests is handled exactly,
    never silently merged.
    """
    d = np.ascontiguousarray(digests, dtype=np.uint8)
    n = len(d)
    if n == 0:
        return d.reshape(0, DIGEST_BYTES), np.empty(0, np.int64)
    k0 = d.view("<u8")[:, 0]
    order = np.argsort(k0, kind="stable").astype(np.int64)
    sk = k0[order]
    bounds = np.nonzero(np.concatenate(([True], sk[1:] != sk[:-1])))[0]
    if len(bounds) == n:  # every first word unique: nothing to resolve
        return d, np.arange(n, dtype=np.int64)
    keep = np.ones(n, dtype=bool)
    bounds = np.append(bounds, n)
    for ri in np.nonzero(np.diff(bounds) > 1)[0]:
        run = order[bounds[ri]:bounds[ri + 1]]  # ascending (stable sort)
        seen: dict[bytes, int] = {}
        for i in run:
            b = d[i].tobytes()
            if b in seen:
                keep[i] = False
            else:
                seen[b] = i
    first = np.nonzero(keep)[0].astype(np.int64)
    return d[first], first


class IndexCursor:
    """Vectorized per-element cursor along the coded-symbol index line.

    Every element's first participation is index 0 (the paper's
    construction: coded symbol 0 sums the whole set).  :meth:`advance`
    yields all (element, index) participations below a bound and leaves
    each element's cursor at its first index >= the bound, so repeated
    calls with growing bounds enumerate each participation exactly once
    — the incremental shape both the builder (extend the prefix) and
    the peeler (recompute a recovered element's cells) need.
    """

    def __init__(self, digests: np.ndarray):
        words = _digest_words(digests)
        self._state = words.view("<u8")[:, 0].astype(np.uint64, copy=True)
        self._next = np.zeros(len(words), dtype=np.uint64)

    def advance(self, bound: int) -> tuple[np.ndarray, np.ndarray]:
        """All pending participations with index < ``bound``:
        ``(element_rows, symbol_indices)`` as int64 arrays."""
        out_e: list[np.ndarray] = []
        out_i: list[np.ndarray] = []
        b = np.uint64(bound)
        active = np.nonzero(self._next < b)[0]
        while active.size:
            idx = self._next[active]
            out_e.append(active.astype(np.int64))
            out_i.append(idx.astype(np.int64))
            # splitmix64 step per active element; the draw's top 32 bits
            # are the uniform r of the gap formula
            st = self._state[active] + _GAMMA
            self._state[active] = st
            r = (_mix64(st) >> np.uint64(32)).astype(np.float64)
            cur = idx.astype(np.float64)
            # inverse-CDF gap for marginal density 1/(1 + i/2):
            # P(next > j | at i) = ((i+1.5)/(j+1.5))^2, u = (r+1)/2^32
            gap = np.ceil(
                (cur + 1.5) * (np.float64(1 << 16) / np.sqrt(r + 1.0) - 1.0)
            )
            self._next[active] = idx + np.maximum(gap, 1.0).astype(np.uint64)
            active = active[self._next[active] < b]
        if not out_e:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        return np.concatenate(out_e), np.concatenate(out_i)


def build_symbols_host(rows: np.ndarray, elems: np.ndarray,
                       idxs: np.ndarray, m: int,
                       base: int = 0) -> np.ndarray:
    """Pure-numpy reference build: scatter-add ``rows[elems]`` into an
    ``(m - base, 11)`` u32 cell block at ``idxs - base``."""
    cells = np.zeros((m - base, SYMBOL_WORDS), dtype=np.uint32)
    np.add.at(cells, idxs - base, rows[elems])
    return cells


def build_symbols_device(rows: np.ndarray, elems: np.ndarray,
                         idxs: np.ndarray, m: int,
                         base: int = 0) -> np.ndarray:
    """The JAX build: one jitted gather + scatter-add over the digest
    columns (u32 adds — byte-identical to the host reference; the
    device story is the same scatter-add shape as
    :func:`.reconcile.sketch_table`).  Update count is bucketed to the
    next power of two (padding aimed at a dump row past the block) so
    batch-size drift cannot recompile per call."""
    import jax

    global _BUILD_JIT
    if _BUILD_JIT is None:
        from ..obs.device import jit_site as _jit_site

        def _build(rows, elems, idxs, nsym: int):
            import jax.numpy as jnp

            # one dump row past the block swallows the padding updates;
            # clip keeps every index in-range regardless of backend OOB
            # semantics.  Cell width comes from the rows themselves
            # (static at trace time), so the SAME program serves both
            # the 11-word unweighted and 12-word weighted layouts.
            table = jnp.zeros((nsym + 1, rows.shape[1]), dtype=jnp.uint32)
            idxs = jnp.minimum(idxs, nsym)
            return table.at[idxs].add(rows[elems])[:nsym]

        _BUILD_JIT = _jit_site("ops.rateless.build",
                               jax.jit(_build, static_argnums=(3,)))
    width = rows.shape[1] if getattr(rows, "ndim", 0) == 2 else SYMBOL_WORDS
    if len(elems) == 0 or len(rows) == 0:
        # nothing to scatter (an empty set, or a fully-covered cursor):
        # the gather below must never index a 0-row array
        return np.zeros((m - base, width), dtype=np.uint32)
    k = len(elems)
    cap = max(16, 1 << (k - 1).bit_length()) if k else 16
    pe = np.zeros(cap, dtype=np.int32)
    pi = np.full(cap, m - base, dtype=np.int32)  # -> the dump row
    pe[:k] = elems
    pi[:k] = idxs - base
    out = _BUILD_JIT(rows, pe, pi, m - base)
    return np.asarray(out)


class CodedSymbols:
    """One replica's incrementally-extended coded-symbol prefix.

    ``extend(m)`` grows the prefix to ``m`` cells, paying only the NEW
    participations (the cursor is incremental), and returns the whole
    ``(m, 11)`` u32 prefix.  Engines (the :class:`.reconcile.LogSummary`
    doctrine — every engine byte-identical, tested):

    * ``'host'`` — the native C one-pass walk+scatter
      (``dat_rateless_build``): digests are host-born bytes and the
      cell block is tiny, so mapping where the bytes live is the
      data-plane route; falls back to the numpy reference without the
      toolchain.
    * ``'numpy'`` — the pure-numpy reference build (the parity oracle).
    * ``'device'`` — the jitted JAX gather + scatter-add over digest
      columns, for pipelines whose digests are already device columns
      (``chip_smoke.py`` checks it against ``'host'`` at 1M digests).
    * ``'auto'`` (default) — ``'host'`` when the native library is
      available, else ``'numpy'``.

    The index mapping is ONE implementation per engine pair: numpy and
    device share :class:`IndexCursor`; the native engine advances the
    SAME cursor arrays in place, so engines can even alternate
    mid-stream without forking the sequence.
    """

    def __init__(self, digests: np.ndarray, engine: str = "auto"):
        if engine not in ("auto", "host", "numpy", "device"):
            raise ValueError(f"unknown engine {engine!r}")
        self.digests = np.ascontiguousarray(digests, dtype=np.uint8)
        self.n = len(self.digests)
        self._rows = None  # numpy/device routes build lazily
        self._cursor = IndexCursor(self.digests)
        self._cells = np.zeros((0, SYMBOL_WORDS), dtype=np.uint32)
        self._engine = engine

    @property
    def rows(self) -> np.ndarray:
        if self._rows is None:
            self._rows = element_rows(self.digests)
        return self._rows

    def _extend_block(self, have: int, m: int) -> np.ndarray:
        if self._engine in ("auto", "host"):
            from ..runtime import native

            block = native.rateless_build(
                self.digests, self._cursor._state, self._cursor._next,
                m, have)
            if block is not None:
                return block
        if self._engine == "device":
            elems, idxs = self._cursor.advance(m)
            return build_symbols_device(self.rows, elems, idxs, m, have)
        elems, idxs = self._cursor.advance(m)
        return build_symbols_host(self.rows, elems, idxs, m, have)

    def extend(self, m: int) -> np.ndarray:
        have = len(self._cells)
        if m <= have:
            return self._cells[:m]
        with span("reconcile.build"):
            block = self._extend_block(have, m)
        self._cells = np.concatenate([self._cells, block]) \
            if have else block
        if _OBS.on:
            _M_SYMBOLS.inc(m - have)
        return self._cells


def _neg(cells: np.ndarray) -> np.ndarray:
    """Word-wise negation mod 2**32."""
    return (np.uint32(0) - cells).astype(np.uint32)


def _counts_i32(cells: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(cells[:, 0]).view(np.int32)


def peel(work: np.ndarray,
         max_rounds: int = 1 << 20,
         ) -> tuple[np.ndarray, np.ndarray, bool]:
    """Peel a combined (remote − local) cell block IN PLACE.

    Returns ``(digests (k, 32) u8, signs (k,) int8, complete)`` —
    ``sign +1``: element held only by the remote (symbol-sending) side,
    ``−1``: only by the local side.  ``complete`` is True iff every
    cell is zero after peeling: the decoded set IS the symmetric
    difference (a nonzero residue means more symbols are needed).
    Each round is vectorized over all currently-pure cells; the
    sequential tail is just the same loop at small widths.
    """
    m = len(work)
    rec_digests: list[np.ndarray] = []
    rec_signs: list[np.ndarray] = []
    with span("reconcile.peel"):
        for _ in range(max_rounds):
            cnt = _counts_i32(work)
            cand = np.nonzero((cnt == 1) | (cnt == -1))[0]
            if not cand.size:
                break
            signs = np.where(cnt[cand] == 1, 1, -1).astype(np.int8)
            sums = work[cand, 3:]
            css = work[cand, 1:3]
            negm = signs == -1
            if negm.any():
                sums = sums.copy()
                css = css.copy()
                sums[negm] = _neg(sums[negm])
                css[negm] = _neg(css[negm])
            ok = (checksum_words(sums) == css).all(axis=1)
            if not ok.any():
                break
            vals = np.ascontiguousarray(sums[ok], dtype=np.uint32)
            signs = signs[ok]
            digests = vals.view(np.uint8).reshape(-1, DIGEST_BYTES)
            # the same element is often pure in several cells at once
            digests, first = dedupe_digests(digests)
            signs = signs[first]
            rows = element_rows(digests)
            srows = rows.copy()
            if (signs == -1).any():
                srows[signs == -1] = _neg(rows[signs == -1])
            elems, idxs = IndexCursor(digests).advance(m)
            np.subtract.at(work, idxs, srows[elems])
            rec_digests.append(digests)
            rec_signs.append(signs)
    if rec_digests:
        digests = np.concatenate(rec_digests)
        signs = np.concatenate(rec_signs)
    else:
        digests = np.empty((0, DIGEST_BYTES), np.uint8)
        signs = np.empty(0, np.int8)
    complete = not work.any()
    if _OBS.on and len(digests):
        _M_PEELED.inc(len(digests))
    return digests, signs, complete


class PeelDecoder:
    """The receiving half of a rateless reconciliation.

    Accumulates the remote side's coded-symbol runs, maintains the
    matching local prefix, and :meth:`try_decode` attempts a full peel
    of the combined cells.  Decode state is monotone — runs must arrive
    contiguously from index 0 (the wire framing enforces ordering; a
    gap is a caller bug and raises)."""

    def __init__(self, local_digests: np.ndarray, engine: str = "auto",
                 assume_unique: bool = False):
        digests = np.ascontiguousarray(local_digests, dtype=np.uint8)
        if not assume_unique:  # a caller with deduped state skips the sort
            digests, _ = dedupe_digests(digests)
        self.local = CodedSymbols(digests, engine=engine)
        self._remote = np.zeros((0, SYMBOL_WORDS), dtype=np.uint32)
        self.symbols_seen = 0

    def add_symbols(self, start: int, cells: np.ndarray) -> None:
        cells = np.ascontiguousarray(cells, dtype=np.uint32)
        if cells.ndim != 2 or cells.shape[1] != SYMBOL_WORDS:
            raise ValueError("cells must be (k, 11) u32")
        if start != self.symbols_seen:
            raise ValueError(
                f"symbol run starts at {start}, expected {self.symbols_seen}"
            )
        self._remote = np.concatenate([self._remote, cells]) \
            if self.symbols_seen else cells
        self.symbols_seen = len(self._remote)

    def try_decode(self):
        """One decode attempt over everything received.

        ``None`` when more symbols are needed; otherwise
        ``(digests, signs)`` — sign +1: remote-only, −1: local-only."""
        m = self.symbols_seen
        if m == 0:
            return None
        local = self.local.extend(m)
        work = (self._remote - local).astype(np.uint32)
        digests, signs, complete = peel(work)
        if not complete:
            return None
        return digests, signs


# -- weighted (variable-size element) extension ------------------------------
#
# The snapshot bootstrap (ISSUE 12) reconciles CDC chunk SETS, whose
# elements carry a byte length.  The construction below is the
# "Rateless Bloom Filters" variable-size extension of everything above:
# same splitmix64 draw stream, same index line, but (a) the cell grows
# a wrapping-u32 LENGTH word (and the checksum chain covers it), and
# (b) index gaps divide by ``weight_class + 1`` so heavy chunks
# participate more densely and decode earlier.  Both additions preserve
# the recoverability invariant: a pure cell's sum IS (digest, length),
# and the weighted cursor is recomputable from that pair alone.


def weight_classes(lens) -> np.ndarray:
    """Weight class per element: ``min(RATELESS_W_CAP,
    bit_length(len >> RATELESS_W_SHIFT))`` as uint64 — pure integer
    math, bit-identical across engines (the native twin runs the same
    shift loop)."""
    v = np.asarray(lens, dtype=np.uint64) >> np.uint64(RATELESS_W_SHIFT)
    c = np.zeros(len(v), dtype=np.uint64)
    for _ in range(RATELESS_W_CAP):
        nz = v > 0
        if not nz.any():
            break
        c[nz] += np.uint64(1)
        v = v >> np.uint64(1)
    return c


def _as_len_words(lens) -> np.ndarray:
    lens = np.asarray(lens)
    arr = lens.astype(np.int64, copy=False)
    if len(arr) and (arr < 0).any():
        raise ValueError("element lengths must be >= 0")
    if len(arr) and (arr >> 32).any():
        raise ValueError("element lengths must fit in u32")
    return arr.astype(np.uint32)


def weighted_checksum_words(sum_words: np.ndarray,
                            len_words: np.ndarray) -> np.ndarray:
    """64-bit checksum of each (digest, length) row as (n, 2) u32 words:
    the :func:`checksum_words` chain extended by one mix over the
    length word, so a cell whose sum and length were perturbed together
    still fails the pure test."""
    w = np.ascontiguousarray(sum_words, dtype=np.uint32)
    lanes = w.view("<u8")
    acc = _mix64(lanes[:, 0] + _GAMMA)
    for k in range(1, 4):
        acc = _mix64(acc ^ lanes[:, k])
    acc = _mix64(acc ^ np.asarray(len_words, np.uint32).astype(np.uint64))
    out = np.empty((len(w), 2), dtype=np.uint32)
    out[:, 0] = (acc & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    out[:, 1] = (acc >> np.uint64(32)).astype(np.uint32)
    return out


def weighted_element_rows(digests: np.ndarray, lens) -> np.ndarray:
    """(n, 32) u8 digests + lengths -> (n, 12) u32 weighted symbol rows
    (count=1)."""
    words = _digest_words(digests)
    lw = _as_len_words(lens)
    if len(lw) != len(words):
        raise ValueError("digests and lens must align")
    rows = np.empty((len(words), WSYMBOL_WORDS), dtype=np.uint32)
    rows[:, 0] = 1
    rows[:, 1:3] = weighted_checksum_words(words, lw)
    rows[:, 3:11] = words
    rows[:, 11] = lw
    return rows


class WeightedIndexCursor:
    """:class:`IndexCursor` for (digest, length) elements: the SAME
    splitmix64 draw stream and gap formula, with the drawn gap divided
    (integer division, then clamped to >= 1) by ``weight_class + 1`` —
    the one owner of the weighted float math, shared by the numpy and
    device routes; the native engine advances the same arrays in
    place."""

    def __init__(self, digests: np.ndarray, lens):
        words = _digest_words(digests)
        lw = _as_len_words(lens)
        if len(lw) != len(words):
            raise ValueError("digests and lens must align")
        self._state = words.view("<u8")[:, 0].astype(np.uint64, copy=True)
        self._next = np.zeros(len(words), dtype=np.uint64)
        self._div = weight_classes(lw) + np.uint64(1)

    def advance(self, bound: int) -> tuple[np.ndarray, np.ndarray]:
        out_e: list[np.ndarray] = []
        out_i: list[np.ndarray] = []
        b = np.uint64(bound)
        active = np.nonzero(self._next < b)[0]
        while active.size:
            idx = self._next[active]
            out_e.append(active.astype(np.int64))
            out_i.append(idx.astype(np.int64))
            st = self._state[active] + _GAMMA
            self._state[active] = st
            r = (_mix64(st) >> np.uint64(32)).astype(np.float64)
            cur = idx.astype(np.float64)
            gap = np.ceil(
                (cur + 1.5) * (np.float64(1 << 16) / np.sqrt(r + 1.0) - 1.0)
            )
            gap_u = np.maximum(gap, 1.0).astype(np.uint64)
            gap_u = np.maximum(gap_u // self._div[active], np.uint64(1))
            self._next[active] = idx + gap_u
            active = active[self._next[active] < b]
        if not out_e:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        return np.concatenate(out_e), np.concatenate(out_i)


class WeightedSymbols:
    """One replica's weighted coded-symbol prefix over a chunk set —
    the :class:`CodedSymbols` shape for (digest, length) elements, same
    three byte-identical engines (native ``dat_rateless_build_w``,
    numpy reference, jitted JAX scatter-add — the device build is the
    SAME cached program, specialized to the 12-word row width)."""

    def __init__(self, digests: np.ndarray, lens, engine: str = "auto"):
        if engine not in ("auto", "host", "numpy", "device"):
            raise ValueError(f"unknown engine {engine!r}")
        self.digests = np.ascontiguousarray(digests, dtype=np.uint8)
        self.lens = np.ascontiguousarray(
            np.asarray(lens, dtype=np.int64))
        self.n = len(self.digests)
        self._rows = None
        self._cursor = WeightedIndexCursor(self.digests, self.lens)
        self._cells = np.zeros((0, WSYMBOL_WORDS), dtype=np.uint32)
        self._engine = engine
        # unlike CodedSymbols (one per reconcile session), a weighted
        # prefix is SHARED per snapshot manifest across concurrent
        # responder sessions — extend() is a read-modify-write of the
        # in-place cursor arrays (the native engine mutates them too),
        # so it must serialize
        self._lock = threading.Lock()

    @property
    def rows(self) -> np.ndarray:
        if self._rows is None:
            self._rows = weighted_element_rows(self.digests, self.lens)
        return self._rows

    def _extend_block(self, have: int, m: int) -> np.ndarray:
        if self._engine in ("auto", "host"):
            from ..runtime import native

            block = native.rateless_build_w(
                self.digests, self.lens, self._cursor._state,
                self._cursor._next, m, have)
            if block is not None:
                return block
        elems, idxs = self._cursor.advance(m)
        if self._engine == "device":
            return build_symbols_device(self.rows, elems, idxs, m, have)
        cells = np.zeros((m - have, WSYMBOL_WORDS), dtype=np.uint32)
        np.add.at(cells, idxs - have, self.rows[elems])
        return cells

    def extend(self, m: int) -> np.ndarray:
        with self._lock:
            have = len(self._cells)
            if m <= have:
                return self._cells[:m]
            with span("reconcile.build"):
                # holding the prefix lock ACROSS the build is the
                # design (see __init__): extension is a read-modify-
                # write of shared cursor arrays, and every concurrent
                # responder needs exactly this block's result — there
                # is nothing useful to do but wait.  Includes the
                # first caller's one-time native-engine build.
                # datlint: allow-blocking-under-lock
                block = self._extend_block(have, m)
            self._cells = np.concatenate([self._cells, block]) \
                if have else block
            if _OBS.on:
                _M_SYMBOLS.inc(m - have)
            return self._cells


def peel_weighted(work: np.ndarray, max_rounds: int = 1 << 20,
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray, bool]:
    """:func:`peel` for weighted cells, IN PLACE.  Returns
    ``(digests (k, 32) u8, lens (k,) int64, signs (k,) int8, complete)``
    — the recovered elements carry their byte lengths."""
    m = len(work)
    rec_digests: list[np.ndarray] = []
    rec_lens: list[np.ndarray] = []
    rec_signs: list[np.ndarray] = []
    with span("reconcile.peel"):
        for _ in range(max_rounds):
            cnt = _counts_i32(work)
            cand = np.nonzero((cnt == 1) | (cnt == -1))[0]
            if not cand.size:
                break
            signs = np.where(cnt[cand] == 1, 1, -1).astype(np.int8)
            sums = work[cand, 3:11]
            lenw = work[cand, 11]
            css = work[cand, 1:3]
            negm = signs == -1
            if negm.any():
                sums = sums.copy()
                css = css.copy()
                lenw = lenw.copy()
                sums[negm] = _neg(sums[negm])
                css[negm] = _neg(css[negm])
                lenw[negm] = (np.uint32(0) - lenw[negm]).astype(np.uint32)
            ok = (weighted_checksum_words(sums, lenw) == css).all(axis=1)
            if not ok.any():
                break
            vals = np.ascontiguousarray(sums[ok], dtype=np.uint32)
            signs = signs[ok]
            lens = lenw[ok].astype(np.int64)
            digests = vals.view(np.uint8).reshape(-1, DIGEST_BYTES)
            digests, first = dedupe_digests(digests)
            signs = signs[first]
            lens = lens[first]
            rows = weighted_element_rows(digests, lens)
            srows = rows.copy()
            if (signs == -1).any():
                srows[signs == -1] = _neg(rows[signs == -1])
            elems, idxs = WeightedIndexCursor(digests, lens).advance(m)
            np.subtract.at(work, idxs, srows[elems])
            rec_digests.append(digests)
            rec_lens.append(lens)
            rec_signs.append(signs)
    if rec_digests:
        digests = np.concatenate(rec_digests)
        lens = np.concatenate(rec_lens)
        signs = np.concatenate(rec_signs)
    else:
        digests = np.empty((0, DIGEST_BYTES), np.uint8)
        lens = np.empty(0, np.int64)
        signs = np.empty(0, np.int8)
    complete = not work.any()
    if _OBS.on and len(digests):
        _M_PEELED.inc(len(digests))
    return digests, lens, signs, complete


class WeightedPeelDecoder:
    """The receiving half of a weighted (chunk-set) reconciliation —
    :class:`PeelDecoder` over (digest, length) elements."""

    def __init__(self, local_digests: np.ndarray, local_lens,
                 engine: str = "auto", assume_unique: bool = False):
        digests = np.ascontiguousarray(local_digests, dtype=np.uint8)
        lens = np.ascontiguousarray(np.asarray(local_lens, dtype=np.int64))
        if not assume_unique:
            digests, first = dedupe_digests(digests)
            lens = lens[first]
        self.local = WeightedSymbols(digests, lens, engine=engine)
        self._remote = np.zeros((0, WSYMBOL_WORDS), dtype=np.uint32)
        self.symbols_seen = 0

    def add_symbols(self, start: int, cells: np.ndarray) -> None:
        cells = np.ascontiguousarray(cells, dtype=np.uint32)
        if cells.ndim != 2 or cells.shape[1] != WSYMBOL_WORDS:
            raise ValueError(f"cells must be (k, {WSYMBOL_WORDS}) u32")
        if start != self.symbols_seen:
            raise ValueError(
                f"symbol run starts at {start}, expected {self.symbols_seen}"
            )
        self._remote = np.concatenate([self._remote, cells]) \
            if self.symbols_seen else cells
        self.symbols_seen = len(self._remote)

    def try_decode(self):
        """``None`` when more symbols are needed; otherwise
        ``(digests, lens, signs)`` — sign +1: remote-only (the chunks
        this side is missing), −1: local-only."""
        m = self.symbols_seen
        if m == 0:
            return None
        local = self.local.extend(m)
        work = (self._remote - local).astype(np.uint32)
        digests, lens, signs, complete = peel_weighted(work)
        if not complete:
            return None
        return digests, lens, signs
