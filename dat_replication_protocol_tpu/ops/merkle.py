"""Merkle tree build + two-snapshot diff as jitted device ops.

The reference has no Merkle machinery — resumable replication lives in dat
core above the wire protocol (reference: messages/schema.proto:4-5 carries
``from``/``to`` version fields for it).  The TPU-native framework pulls set
reconciliation into the data plane (BASELINE.json north star: "Merkle-tree
diff of two 1M-leaf change-log snapshots", target >= 10M diff entries/sec).

Design (TPU-first):

* A node digest is BLAKE2b-256 of the 64-byte concatenation of its two
  children's 32-byte digests — exactly one BLAKE2b compression per parent,
  so level ``k -> k+1`` is a single batched :func:`..ops.blake2b.compress`
  call over ``N/2`` items.  No data-dependent shapes: a tree over ``2**L``
  leaves is ``L`` static level steps under one jit.
* Digests stay on device in the (hi, lo) uint32 lane-pair layout of
  :mod:`.u64` — ``(N, 4)`` word pairs per level — so building a tree from
  the batched leaf hasher's output involves no host round-trip and no
  byte re-packing.
* The diff is **tree-guided and fully vectorized**: walking top-down, a
  level's inequality mask is AND-ed with its parent's mask repeated over
  children.  Equal subtrees are masked out in O(1) vector work per level
  rather than skipped via control flow — the XLA-friendly formulation of
  the classic "descend only into differing nodes" walk.  The kernel
  returns a leaf mask; dynamic-shape index extraction happens on the host.

Host-reference implementations (``host_*``) back the property tests.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.device import jit_site as _jit_site

from .blake2b import compress, initial_state
from .u64 import U32

DIGEST_SIZE = 32
_DIGEST_WORDS = 4  # 32 bytes = 4 x u64 lane pairs


def merkle_parent(ahh, ahl, bhh, bhl):
    """Hash pairs of sibling digests into parents: all (N, 4) uint32.

    Parent = BLAKE2b-256(child_left || child_right): a 64-byte message,
    one compression block per parent, vectorized over all N pairs.

    Uses the scanned-rounds compression: a tree build instantiates this
    op once per level, and the unrolled ~5k-op variant makes 20-level
    tree programs pathologically slow to compile (XLA chokes past ~100k
    ops); the scanned form keeps a whole build+diff program around ~3k
    ops for a ~2x runtime cost that the fixed-width scan below already
    amortizes.
    """
    n = ahh.shape[0]
    zeros = jnp.zeros((n, 16), dtype=U32)
    mh = zeros.at[:, :4].set(ahh).at[:, 4:8].set(bhh)
    ml = zeros.at[:, :4].set(ahl).at[:, 4:8].set(bhl)
    hh, hl = initial_state(n, DIGEST_SIZE)
    t_lo = jnp.full((n,), 2 * DIGEST_SIZE, dtype=U32)
    final = jnp.ones((n,), dtype=bool)
    hh, hl = compress(hh, hl, mh, ml, t_lo, final, unroll=False)
    return hh[:, :_DIGEST_WORDS], hl[:, :_DIGEST_WORDS]


def merkle_level(hh, hl):
    """One tree level: (N, 4) digests -> (N//2, 4) parent digests.

    Left/right children are even/odd rows (leaf ``i`` pairs with ``i^1``,
    dat's flat in-order convention).
    """
    return merkle_parent(hh[0::2], hl[0::2], hh[1::2], hl[1::2])


# below this parent count the Pallas kernel's pad-to-1024-items overhead
# outweighs its edge over the scanned XLA path (and small levels are a
# rounding error of the tree's total work anyway)
_PALLAS_MIN_PARENTS = 8192


def _merkle_level_opt(hh, hl):
    """Level step routed to the fastest available engine.

    Large levels on TPU go through the dedicated single-block Pallas
    kernel (:mod:`.merkle_pallas`), which retires the scanned-rounds
    compile-time compromise of :func:`merkle_parent` exactly where its
    ~2x runtime cost was actually felt; small levels and other backends
    keep the portable path.
    """
    if (
        hh.shape[0] // 2 >= _PALLAS_MIN_PARENTS
        and jax.default_backend() == "tpu"
    ):
        from .merkle_pallas import merkle_level_pallas

        return merkle_level_pallas(hh, hl)
    return merkle_level(hh, hl)


@jax.jit
def build_tree(leaf_hh, leaf_hl):
    """All levels leaves -> root. Leaf count must be a power of two.

    Returns (levels_hh, levels_lo): tuples of per-level arrays ordered
    leaves first, root (shape (1, 4)) last.  The level count is static, so
    the whole build is one fused jit program of log2(N) batched
    compressions.
    """
    n = leaf_hh.shape[0]
    if n == 0 or n & (n - 1):
        raise ValueError(f"leaf count {n} is not a power of two; pad first")
    levels_hh, levels_hl = [leaf_hh], [leaf_hl]
    while leaf_hh.shape[0] > 1:
        leaf_hh, leaf_hl = _merkle_level_opt(leaf_hh, leaf_hl)
        levels_hh.append(leaf_hh)
        levels_hl.append(leaf_hl)
    return tuple(levels_hh), tuple(levels_hl)


build_tree = _jit_site("ops.merkle.build_tree", build_tree)


def root(leaf_hh, leaf_hl):
    """Root digest only: (1, 4) hi/lo word pairs."""
    hhs, hls = build_tree(leaf_hh, leaf_hl)
    return hhs[-1], hls[-1]


def _node_neq(ahh, ahl, bhh, bhl):
    """(N,) bool: per-node digest inequality."""
    return jnp.any((ahh != bhh) | (ahl != bhl), axis=1)


@jax.jit
def diff_root_guided(a_leaf_hh, a_leaf_hl, b_leaf_hh, b_leaf_hl):
    """Build both trees and diff them in one jitted program.

    Returns (mask, a_root_pair, b_root_pair): two snapshots' leaf
    digests in, differing-leaf mask out (BASELINE.json configs[4]).

    Both trees are built as ONE concatenated tree: with a power-of-two
    leaf width, the even/odd sibling pairing never crosses the midpoint
    of ``concat(a, b)``, so each combined level's halves are exactly the
    two trees' levels.  One level-op chain instead of two halves the
    per-level dispatch overhead, doubles every batch (the small top
    levels were pure fixed cost), and lifts twice as many levels over
    the Pallas kernel's minimum-parents threshold.
    """
    n = a_leaf_hh.shape[0]
    if n == 0 or n & (n - 1):
        raise ValueError(f"leaf count {n} is not a power of two; pad first")
    if b_leaf_hh.shape[0] != n:
        raise ValueError(
            f"snapshot widths differ: {n} vs {b_leaf_hh.shape[0]}; pad first"
        )
    hh = jnp.concatenate([a_leaf_hh, b_leaf_hh])
    hl = jnp.concatenate([a_leaf_hl, b_leaf_hl])
    levels = []
    while hh.shape[0] > 2:
        levels.append((hh, hl))
        hh, hl = _merkle_level_opt(hh, hl)
    # hh/hl is now (2, 4): row 0 = A's root, row 1 = B's root
    mask = _node_neq(hh[:1], hl[:1], hh[1:], hl[1:])
    for lhh, lhl in reversed(levels):
        half = lhh.shape[0] // 2
        mask = jnp.repeat(mask, 2) & _node_neq(
            lhh[:half], lhl[:half], lhh[half:], lhl[half:]
        )
    return mask, (hh[:1], hl[:1]), (hh[1:], hl[1:])


diff_root_guided = _jit_site("ops.merkle.diff_root_guided", diff_root_guided)


@jax.jit
def update_leaves(levels_hh, levels_hl, idx, new_hh, new_hl):
    """Incrementally apply K leaf updates to a built tree.

    The replication data plane's steady state is "a small change batch
    lands on a big snapshot": rebuilding a 2**20-leaf tree for a K-leaf
    batch wastes N/K of the work.  This op scatters the new leaf digests
    and recomputes only the K root-paths — K compressions per level,
    log2(N) levels, all fixed shapes (duplicate parents among the K
    paths are recomputed redundantly and scattered to the same value, so
    no host-side dedup or dynamic shapes are needed).

    ``levels_hh/hl``: tuples from :func:`build_tree` (leaves first, root
    last); ``idx``: (K,) int32 leaf positions; ``new_hh/hl``: (K, 4)
    replacement digests.  Returns new level tuples.  Cost: O(K log N)
    vs O(N) rebuild — at K=1024, N=2**20 that is ~50x less hashing.
    """
    idx = jnp.asarray(idx, dtype=jnp.int32)
    new_levels_hh = [levels_hh[0].at[idx].set(new_hh)]
    new_levels_hl = [levels_hl[0].at[idx].set(new_hl)]
    for lvl in range(1, len(levels_hh)):
        child_hh = new_levels_hh[-1]
        child_hl = new_levels_hl[-1]
        pidx = idx >> 1
        left = pidx * 2
        p_hh, p_hl = merkle_parent(
            child_hh[left], child_hl[left],
            child_hh[left + 1], child_hl[left + 1],
        )
        new_levels_hh.append(levels_hh[lvl].at[pidx].set(p_hh))
        new_levels_hl.append(levels_hl[lvl].at[pidx].set(p_hl))
        idx = pidx
    return tuple(new_levels_hh), tuple(new_levels_hl)


update_leaves = _jit_site("ops.merkle.update_leaves", update_leaves)


@jax.jit
def diff_root_guided_packed(a_leaf_hh, a_leaf_hl, b_leaf_hh, b_leaf_hl):
    """:func:`diff_root_guided` with the leaf mask packed 32 bools/word.

    The D2H transfer is the tail of the diff's critical path (1 bit per
    leaf instead of numpy's byte-per-bool — 8x less D2H volume).
    Expand on the host with :func:`unpack_mask`.
    """
    mask, root_a, root_b = diff_root_guided(
        a_leaf_hh, a_leaf_hl, b_leaf_hh, b_leaf_hl
    )
    n = mask.shape[0]
    if n % 32:
        mask = jnp.pad(mask, (0, 32 - n % 32))
    bits = jnp.sum(
        mask.reshape(-1, 32).astype(U32) << jnp.arange(32, dtype=U32)[None, :],
        axis=1,
    )
    return bits, root_a, root_b


diff_root_guided_packed = _jit_site(
    "ops.merkle.diff_root_guided_packed", diff_root_guided_packed
)


# ---------------------------------------------------------------------------
# host edge
# ---------------------------------------------------------------------------


def unpack_mask(bits, n: int) -> np.ndarray:
    """Expand a packed device mask (uint32 words, LSB-first) to (n,) bools.

    The single host-side decode for every packed-mask producer
    (:func:`diff_root_guided_packed`, the reconcile sketch diff, the CDC
    occupancy transfer): one place owns the bit order.
    """
    dense = np.unpackbits(
        np.asarray(bits, dtype=np.uint32).view(np.uint8), bitorder="little"
    )
    return dense[:n]


def digests_to_device(digests: list[bytes]):
    """Pack 32-byte digests into (N, 4) hi/lo uint32 device arrays.

    Inverse of :func:`digests_to_words` / the first 4 word pairs of
    :func:`..ops.blake2b.digests_to_bytes`'s layout (little-endian u64
    words as (hi, lo) u32 pairs).
    """
    raw = np.frombuffer(b"".join(digests), dtype="<u4").reshape(-1, 8)
    return jnp.asarray(raw[:, 1::2].copy()), jnp.asarray(raw[:, 0::2].copy())


def digest_matrix(hh, hl) -> np.ndarray:
    """(N, 4) hi/lo word pairs -> (N, 32) uint8 digest bytes — the ONE
    owner of the little-endian lo/hi word interleave (word k's low half
    at byte 8k, high half at 8k+4)."""
    hh = np.asarray(hh, dtype=np.uint32)
    hl = np.asarray(hl, dtype=np.uint32)
    out = np.empty((hh.shape[0], 8), dtype="<u4")
    out[:, 0::2] = hl
    out[:, 1::2] = hh
    return out.view(np.uint8).reshape(hh.shape[0], 32)


def digests_from_device(hh, hl) -> list[bytes]:
    """(N, 4) hi/lo word pairs -> list of 32-byte digests."""
    raw = digest_matrix(hh, hl)
    return [raw[i].tobytes() for i in range(raw.shape[0])]


def root_host(digests: np.ndarray) -> bytes:
    """Merkle root of (N, 32) uint8 leaf digests on the HOST engine.

    Byte-identical to ``digests_from_device(*root(*pad_leaves(...)))``
    (same zero-digest padding, same pair convention — tested), but the
    level fold runs through the native thread-parallel BLAKE2b engine
    instead of an XLA program: on a CPU-backed jax the device fold's
    scanned-rounds compression measured ~0.01 GiB/s end-to-end, turning
    the single-pass :func:`..runtime.content.content_address` host route
    back into a two-order-of-magnitude cliff.  "Batch or stay home"
    applies to the tree fold too.
    """
    from ..runtime import native

    n = len(digests)
    if n == 0:
        return b"\0" * DIGEST_SIZE
    p = 1
    while p < n:
        p <<= 1
    level = np.zeros((p, DIGEST_SIZE), dtype=np.uint8)
    level[:n] = digests
    while len(level) > 1:
        pairs = np.ascontiguousarray(level).reshape(-1)
        half = len(level) // 2
        offs = np.arange(half, dtype=np.int64) * (2 * DIGEST_SIZE)
        lens = np.full(half, 2 * DIGEST_SIZE, dtype=np.int64)
        out = native.hash_many(pairs, offs, lens)
        if out is None:  # no native library: hashlib loop
            out = np.empty((half, DIGEST_SIZE), dtype=np.uint8)
            for i in range(half):
                out[i] = np.frombuffer(
                    host_parent(level[2 * i].tobytes(),
                                level[2 * i + 1].tobytes()),
                    dtype=np.uint8,
                )
        level = out
    return level[0].tobytes()


def pad_leaves(hh, hl):
    """Zero-pad the leaf axis up to the next power of two.

    Zero digests act as the empty-subtree sentinel; both snapshots of a
    diff must be padded to the same width (the parallel layer always
    compares equal-width snapshots).
    """
    n = hh.shape[0]
    p = 1
    while p < n:
        p <<= 1
    if p == n:
        return hh, hl
    pad = ((0, p - n), (0, 0))
    return jnp.pad(hh, pad), jnp.pad(hl, pad)


def diff_leaves(a_digests: list[bytes], b_digests: list[bytes]) -> list[int]:
    """Host-friendly wrapper: digests in, differing leaf indices out."""
    if len(a_digests) != len(b_digests):
        raise ValueError("snapshots must have equal leaf counts; pad first")
    if not a_digests:
        return []
    a_hh, a_hl = pad_leaves(*digests_to_device(a_digests))
    b_hh, b_hl = pad_leaves(*digests_to_device(b_digests))
    mask, _, _ = diff_root_guided(a_hh, a_hl, b_hh, b_hl)
    return np.nonzero(np.asarray(mask)[: len(a_digests)])[0].tolist()


def diff_snapshots(a_hh, a_hl, b_hh, b_hl) -> np.ndarray:
    """Differing leaf indices between two LOCAL equal-width snapshots,
    routed by backend ("batch or stay home", DESIGN.md §2 rule 0):

    * accelerator-backed jax — the tree-guided packed diff
      (:func:`diff_root_guided_packed`): compare work stays in HBM and
      one bit per leaf crosses D2H;
    * CPU-backed jax — one vectorized elementwise compare: when both
      snapshots already sit in host memory the tree build buys nothing
      locally (the O(diff · log n) walk is the *device* and *remote*
      story — :mod:`..runtime.tree_sync` for the wire).

    ``DAT_DEVICE_MERKLE=1/0`` overrides.  Both paths return identical
    indices (tested).
    """
    from ..utils.routing import prefer_host

    n = a_hh.shape[0]
    if b_hh.shape[0] != n:
        raise ValueError("snapshots must have equal (padded) leaf counts")
    if n & (n - 1):
        # enforce the device branch's precondition on BOTH paths: code
        # developed against the host compare must not start crashing the
        # moment it runs on an accelerator
        raise ValueError(f"leaf count {n} is not a power of two; pad first")
    if prefer_host("DAT_DEVICE_MERKLE"):
        a1, a2 = np.asarray(a_hh), np.asarray(a_hl)
        b1, b2 = np.asarray(b_hh), np.asarray(b_hl)
        dense = ((a1 != b1) | (a2 != b2)).any(axis=1)
        return np.nonzero(dense)[0]
    bits, _, _ = diff_root_guided_packed(a_hh, a_hl, b_hh, b_hl)
    return np.nonzero(unpack_mask(bits, n))[0]


def prove(levels_hh, levels_hl, idx: int) -> list[bytes]:
    """Inclusion proof for leaf ``idx``: the sibling digest per level.

    ``levels_hh/hl``: the tuples from :func:`build_tree`.  The path has
    log2(N) 32-byte siblings, bottom-up; verification needs only the
    root (:func:`verify_proof`) — the content-addressed audit primitive
    a replica uses to check a single record against a snapshot root
    without holding the snapshot (the reference leaves all verification
    to dat core above the wire; here it rides the device-built tree).
    Only the log2(N) sibling rows cross D2H.
    """
    n = levels_hh[0].shape[0]
    if not 0 <= idx < n:
        raise IndexError(f"leaf {idx} out of range [0, {n})")
    nlev = len(levels_hh) - 1
    if nlev == 0:
        return []
    # gather all log2(N) sibling rows on device, one D2H transfer (per-
    # level fetches would pay one host round trip each)
    sib_hh = jnp.concatenate(
        [levels_hh[lvl][((idx >> lvl) ^ 1)][None] for lvl in range(nlev)]
    )
    sib_hl = jnp.concatenate(
        [levels_hl[lvl][((idx >> lvl) ^ 1)][None] for lvl in range(nlev)]
    )
    return digests_from_device(sib_hh, sib_hl)


def verify_proof(root: bytes, leaf: bytes, idx: int,
                 path: list[bytes], nleaves: int) -> bool:
    """Check an inclusion proof against a 32-byte root (host, hashlib).

    ``nleaves`` is the tree width the verifier expects (it knows the
    snapshot's size alongside its root) and is load-bearing, not
    advisory: without it, (a) an attacker-chosen shorter path would
    bind against the *subtree* an interior node roots — any interior
    digest would "verify" as a leaf (second-preimage aliasing; the
    depth check pins len(path) to the padded tree height) — and (b)
    indices would alias mod 2**len(path), verifying forged claims at
    positions outside the snapshot.
    """
    if nleaves <= 0 or not 0 <= idx < nleaves:
        return False
    depth = max(0, (int(nleaves) - 1)).bit_length()  # padded tree height
    if len(path) != depth:
        return False
    node = leaf
    for lvl, sib in enumerate(path):
        bit = (idx >> lvl) & 1
        node = host_parent(sib, node) if bit else host_parent(node, sib)
    return node == root


# ---------------------------------------------------------------------------
# host reference (for tests)
# ---------------------------------------------------------------------------


def host_parent(left: bytes, right: bytes) -> bytes:
    return hashlib.blake2b(left + right, digest_size=DIGEST_SIZE).digest()


def host_tree(leaves: list[bytes]) -> list[list[bytes]]:
    levels = [list(leaves)]
    while len(levels[-1]) > 1:
        prev = levels[-1]
        levels.append(
            [host_parent(prev[i], prev[i + 1]) for i in range(0, len(prev), 2)]
        )
    return levels


def host_diff(a: list[bytes], b: list[bytes]) -> list[int]:
    """Recursive descend-on-difference reference diff."""
    out: list[int] = []

    def walk(ta, tb, lvl, idx):
        if ta[lvl][idx] == tb[lvl][idx]:
            return
        if lvl == 0:
            out.append(idx)
            return
        walk(ta, tb, lvl - 1, 2 * idx)
        walk(ta, tb, lvl - 1, 2 * idx + 1)

    ta, tb = host_tree(a), host_tree(b)
    walk(ta, tb, len(ta) - 1, 0)
    return out
