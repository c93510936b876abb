"""Sequence-parallel content-defined chunking over a device mesh.

The long-context discipline of this framework (SURVEY.md §5: blobs
stream in O(chunk) memory) scales across chips the same way sequence /
context parallelism scales attention: the byte stream is sharded into
contiguous spans, each chip scans its span locally, and the only
cross-chip traffic is a GROUP-wide (256-byte) **halo** row at each span
boundary, of which the last WINDOW=64 bytes are the real rolling-hash
context — a single ``ppermute`` neighbor exchange over ICI, the
ring-attention communication pattern reduced to its minimal case (the
gear hash forgets beyond WINDOW bytes, so one fixed-size halo replaces
ring attention's full KV rotation).

Layout: the caller tiles the stream exactly like :mod:`..ops.rabin` —
rows of ``[GROUP context | stride payload]`` — but the row axis is
sharded over the mesh's data axis.  Each shard builds its local rows
from its local payload slab plus the halo row received from its left
neighbor, then runs the same tiled gear scan every single-chip path
uses (zero-seeded per row; rows are independent by construction, which
is what makes the whole scan embarrassingly parallel after the halo).

This module is deliberately thin: the kernels and extraction live in
:mod:`..ops.rabin`; only the halo exchange and the shard_map plumbing
are mesh-specific.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..obs.device import jit_site as _jit_site
from ..ops.rabin import GROUP, _PREFIX_WORDS, gear_candidates_tiled
from ..ops.u64 import U32
from .mesh import DATA_AXIS, Mesh


@functools.lru_cache(maxsize=None)
def _scan_program(mesh: Mesh, avg_bits: int, use_pallas: bool):
    n_dev = mesh.devices.size

    def step(payload, pre_row):
        """``payload``: (T_local, sw) uint32 payload rows of this shard's
        contiguous span; ``pre_row``: (1, 64) uint32 — the stream-global
        seed row (zeros + WINDOW context), used by shard 0 only.
        """
        idx = jax.lax.axis_index(DATA_AXIS)
        # halo: my last row's context tail -> right neighbor
        tail = payload[-1:, -_PREFIX_WORDS:]
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        left_tail = jax.lax.ppermute(tail, DATA_AXIS, perm)
        first_ctx = jnp.where(idx == 0, pre_row, left_tail)
        ctx = jnp.concatenate(
            [first_ctx, payload[:-1, -_PREFIX_WORDS:]], axis=0
        )
        rows = jnp.concatenate([ctx, payload], axis=1)
        if use_pallas:
            from ..ops.rabin_pallas import gear_candidates_pallas

            return gear_candidates_pallas(rows, avg_bits)
        return gear_candidates_tiled(rows, avg_bits)

    return _jit_site(
        "parallel.cdc_mesh.scan",
        jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(P(DATA_AXIS), P()),
                out_specs=P(DATA_AXIS),
                check_vma=False,
            )
        ),
    )


def sharded_gear_scan(mesh: Mesh, payload_rows, prefix=None,
                      avg_bits: int = 13, use_pallas: bool | None = None):
    """Candidate bitmask of a sharded byte stream, one halo exchange.

    ``payload_rows``: (T, stride/4) uint32 — the stream's payload tiles
    (row t = bytes [t*stride, (t+1)*stride), zero-padded tail), with T
    divisible by the mesh size; shard over the row axis before or let
    jit move it.  ``prefix``: optional WINDOW bytes preceding the stream
    (16 uint32 words; None = zero seed).  Returns the (T, width/32)
    packed candidate bitmask, sharded like the rows; valid bit-words per
    row are ``[GROUP/32, GROUP/32 + stride/32)`` exactly as on one chip.

    The cross-chip traffic is ONE (1, 64)-word ppermute per scan —
    constant in stream length, the sequence-parallel ideal.
    """
    T, sw = payload_rows.shape
    if (sw * 4) % GROUP:
        raise ValueError(f"stride must be a multiple of {GROUP}")
    n = mesh.devices.size
    if T % n:
        raise ValueError(f"row count {T} not divisible by mesh size {n}")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    pre = jnp.zeros((1, _PREFIX_WORDS), U32)
    if prefix is not None:
        ctx = jnp.asarray(prefix, dtype=U32).reshape(1, -1)
        pre = pre.at[:, -ctx.shape[1]:].set(ctx)
    fn = _scan_program(mesh, avg_bits, use_pallas)
    return fn(payload_rows, pre)
