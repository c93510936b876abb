"""Multi-chip scale-out: sharded digest + Merkle pipeline over a device mesh.

The reference's only transport is a Node stream pair and its only
"parallelism" is head-of-line blob serialization (reference:
encode.js:87-95); it has no distributed backend at all (SURVEY.md §2).
The TPU-native framework scales the data plane the XLA way instead:

* a 1-D ``jax.sharding.Mesh`` over the ``data`` axis shards the blob batch
  (and the Merkle leaf axis) across chips;
* per-chip work — batched BLAKE2b, local Merkle subtree — runs inside
  ``shard_map`` with zero communication;
* the only collectives are an ``all_gather`` of per-chip subtree roots
  (one 32-byte digest per chip, riding ICI) and a ``psum`` of byte
  counters — the whole cross-chip Merkle merge costs O(devices) bytes.

This module is also what ``__graft_entry__.dryrun_multichip`` compiles on a
virtual device mesh: it is the framework's "full step" — payload batch in,
sharded digests + global Merkle root + global counters out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..obs.device import jit_site as _jit_site
from ..ops import merkle
from ..ops.blake2b import blake2b_batch_begin, blake2b_packed
from ..ops.u64 import U32


DATA_AXIS = "data"


def make_mesh(n_devices: int | None = None) -> Mesh:
    """1-D data mesh over the first ``n_devices`` local devices.

    Power-of-two device counts only: the cross-chip Merkle merge builds a
    binary top tree over per-chip roots.
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(f"requested {n_devices} devices, have {len(devs)}")
    if n_devices & (n_devices - 1):
        raise ValueError(f"device count {n_devices} is not a power of two")
    return Mesh(np.asarray(devs[:n_devices]), (DATA_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch / leaf) axis across the data axis."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _merge_roots(root_hh, root_hl):
    """all_gather per-chip roots and finish the top tree, replicated.

    ``root_hh/hl``: (1, 4) local subtree root. Gathered to (n_dev, 4) on
    every chip (32 bytes per chip over ICI), then the log2(n_dev)-level top
    tree is built redundantly everywhere — cheaper than round-tripping a
    tiny tree through one chip.
    """
    g_hh = jax.lax.all_gather(root_hh[0], DATA_AXIS, axis=0)
    g_hl = jax.lax.all_gather(root_hl[0], DATA_AXIS, axis=0)
    return merkle.root(g_hh, g_hl)


def _check_shard(mesh: Mesh, B: int, what: str) -> None:
    n = mesh.devices.size
    per = B // n if n and B % n == 0 else None
    if per is None or per & (per - 1) or per == 0:
        raise ValueError(
            f"{what}: batch size {B} over {n} devices needs a power-of-two "
            f"per-chip shard (got {B}/{n}); pad the batch first "
            f"(:func:`pad_batch` does)"
        )


def pad_batch(mesh: Mesh, mh, ml, lengths):
    """Pad a packed batch so every chip gets a power-of-two shard.

    Padding items are zero-length payloads — valid BLAKE2b inputs whose
    digests land in the padded tail of the leaf axis.  Both replicas of
    a comparison must pad with the same policy (this one: smallest
    ``n_devices * 2**k >= B``) so their Merkle roots stay comparable;
    the caller slices per-item results with the returned original B.

    Returns ``(mh, ml, lengths, B)``.
    """
    from ..utils.num import next_pow2

    n = mesh.devices.size
    B = mh.shape[0]
    Bp = n * next_pow2(-(-B // n))
    if Bp != B:
        pad = ((0, Bp - B),)
        mh = jnp.pad(mh, pad + ((0, 0), (0, 0)))
        ml = jnp.pad(ml, pad + ((0, 0), (0, 0)))
        lengths = jnp.pad(lengths, (0, Bp - B))
    return mh, ml, lengths, B


@functools.lru_cache(maxsize=None)
def _digest_root_program(mesh: Mesh):
    """Jitted sharded digest step, cached per mesh.

    Built once per mesh so repeated per-batch calls hit jax's jit cache
    (a fresh closure per call would retrace and recompile every time).
    """

    def step(mh, ml, lengths):
        hh, hl = blake2b_packed(mh, ml, lengths)
        leaf_hh, leaf_hl = hh[:, :4], hl[:, :4]
        root_hh, root_hl = _merge_roots(*merkle.root(leaf_hh, leaf_hl))
        # exact byte counter without 64-bit lanes: sum the 16-bit halves
        # separately (each partial sum stays < 2**32 for any batch up to
        # 2**16 items) and recombine as hi*2**16 + lo on the host
        lengths = lengths.astype(U32)
        total_lo = jax.lax.psum(jnp.sum(lengths & U32(0xFFFF)), DATA_AXIS)
        total_hi = jax.lax.psum(jnp.sum(lengths >> U32(16)), DATA_AXIS)
        return leaf_hh, leaf_hl, root_hh, root_hl, total_hi, total_lo

    sharded = P(DATA_AXIS)
    rep = P()
    return _jit_site(
        "parallel.mesh.digest_root",
        jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(sharded, sharded, sharded),
                out_specs=(sharded, sharded, rep, rep, rep, rep),
                check_vma=False,
            )
        ),
    )


def digest_root_step(mesh: Mesh, mh, ml, lengths):
    """The sharded full step: padded payload batch in -> digests + root.

    Inputs follow the :func:`..ops.blake2b.blake2b_packed` layout —
    ``mh/ml`` (B, nblocks, 16) uint32 message words, ``lengths`` (B,) —
    with B divisible by the mesh size and a power-of-two per-chip shard
    (the local Merkle fold is a binary tree).  Per chip: hash the local
    shard, fold the local digests into a subtree root.  Cross-chip:
    gather the per-chip roots, finish the top tree, psum the byte
    counter.

    Returns ``(leaf_hh, leaf_hl, root_hh, root_hl, total_bytes)`` where the
    leaf digests stay sharded over the batch axis and the root/counter are
    replicated.  ``total_bytes`` is an exact Python int (recombined from
    16-bit partial sums, immune to uint32 wrap for batches up to 2**16
    items of any size).
    """
    _check_shard(mesh, mh.shape[0], "digest_root_step")
    fn = _digest_root_program(mesh)
    leaf_hh, leaf_hl, root_hh, root_hl, hi, lo = fn(mh, ml, lengths)
    total = (int(hi) << 16) + int(lo)
    return leaf_hh, leaf_hl, root_hh, root_hl, total


def sharded_hash_engine(mesh: Mesh):
    """The served batch engine laid over ``mesh``, as a ``hash_begin``
    for :class:`~..backend.tpu_backend.DigestPipeline`: the hub's
    cross-session digest batch (ISSUE 8) needs no Merkle fold and no
    collective, so it is :func:`..ops.blake2b.blake2b_batch_begin`
    itself — pooled staging, one copy per piece, the Pallas kernel on a
    chip, the stage spans and the bucket table — given the mesh to lay
    each bucket's rows over.  Like that engine it takes a payload in
    pieces (``takes_parts``), so the pipeline joins nothing for it."""
    begin = functools.partial(blake2b_batch_begin, mesh=mesh)
    begin.takes_parts = blake2b_batch_begin.takes_parts
    return begin


@functools.lru_cache(maxsize=None)
def _sharded_diff_program(mesh: Mesh):
    """Jitted sharded diff, cached per mesh (see _digest_root_program)."""

    def step(a_hh, a_hl, b_hh, b_hl):
        mask, (lra_hh, lra_hl), (lrb_hh, lrb_hl) = merkle.diff_root_guided(
            a_hh, a_hl, b_hh, b_hl
        )
        ra = _merge_roots(lra_hh, lra_hl)
        rb = _merge_roots(lrb_hh, lrb_hl)
        return mask, ra[0], ra[1], rb[0], rb[1]

    sharded = P(DATA_AXIS)
    rep = P()
    return _jit_site(
        "parallel.mesh.sharded_diff",
        jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(sharded, sharded, sharded, sharded),
                out_specs=(sharded, rep, rep, rep, rep),
                check_vma=False,
            )
        ),
    )


@functools.lru_cache(maxsize=None)
def _sharded_sketch_program(mesh: Mesh, log2_slots: int):
    """Jitted sharded sketch build, cached per (mesh, slot count)."""

    from ..ops.reconcile import sketch_table

    nslots = 1 << log2_slots

    def step(rec_hh, rec_hl, slots):
        # local partial table via the shared kernel, then: cells are
        # wrapping-u32 sums, so a psum over chips IS the cell combine —
        # order-independent, exact
        return jax.lax.psum(
            sketch_table(rec_hh, rec_hl, slots, nslots), DATA_AXIS
        )

    return _jit_site(
        "parallel.mesh.sharded_sketch",
        jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
                out_specs=P(),
                check_vma=False,
            )
        ),
    )


def sharded_sketch(mesh: Mesh, rec_hh, rec_hl, slots, log2_slots: int):
    """Key-addressed reconciliation sketch built across the mesh.

    ``rec_hh/hl``: (B, 4) record digest word columns (the
    :func:`..batch.feed.hash_extents_device` layout), sharded over
    chips; ``slots``: (B,) cell indices (uint32/int32).  Each chip
    scatter-adds its shard into a local table; one ``psum`` of the
    (nslots, 8) table over ICI yields the replicated global sketch —
    byte-identical to the single-device build
    (:func:`..ops.reconcile._summarize`), because cells are wrapping
    uint32 sums (commutative, associative).

    The batch is zero-padded to the mesh size: a zero digest adds
    nothing to cell 0, so padding rows cannot perturb the sketch.
    """
    if not 0 < log2_slots <= 31:
        raise ValueError("log2_slots must be in [1, 31]")
    n = mesh.devices.size
    B = rec_hh.shape[0]
    if B % n:
        pad = ((0, n - B % n),)
        rec_hh = jnp.pad(rec_hh, pad + ((0, 0),))
        rec_hl = jnp.pad(rec_hl, pad + ((0, 0),))
        slots = jnp.pad(slots, (0, n - B % n))
    fn = _sharded_sketch_program(mesh, log2_slots)
    return fn(rec_hh, rec_hl, jnp.asarray(slots))


def sharded_diff(mesh: Mesh, a_hh, a_hl, b_hh, b_hl):
    """Tree-guided diff of two snapshots with leaves sharded over chips.

    Each chip diffs its local subtree pair (no communication needed for
    the leaf mask — a differing local leaf is decidable locally); the
    global roots are merged over ICI so callers get the replicated
    snapshot digests alongside the sharded mask.

    Returns ``(mask, a_root, b_root)`` with ``mask`` sharded like the
    leaves and each root a replicated ``((1,4),(1,4))`` hi/lo pair.
    """
    _check_shard(mesh, a_hh.shape[0], "sharded_diff")
    fn = _sharded_diff_program(mesh)
    mask, ra_hh, ra_hl, rb_hh, rb_hl = fn(a_hh, a_hl, b_hh, b_hl)
    return mask, (ra_hh, ra_hl), (rb_hh, rb_hl)
