"""Pallas BLAKE2b kernel vs hashlib, via the interpreter on CPU.

The real Mosaic compile path is checked by ``tests/test_tpu_compile.py``
(compiled for a described chip) and runs on the TPU under
``chip_smoke.py`` and the benchmark; these tests check the kernel's
logic — layout plumbing, state chaining across blocks, variable-length
masks, batch padding — with ``interpret=True`` on tiny shapes.
"""

import hashlib

import jax.numpy as jnp
import pytest

from dat_replication_protocol_tpu.ops.blake2b import (
    digests_to_bytes,
    pack_payloads,
)
from dat_replication_protocol_tpu.ops.blake2b_pallas import (
    blake2b_packed_pallas,
)


def _run(payloads, nblocks=None):
    mh, ml, lengths = pack_payloads(payloads, nblocks=nblocks)
    hh, hl = blake2b_packed_pallas(
        jnp.asarray(mh), jnp.asarray(ml), jnp.asarray(lengths), interpret=True
    )
    return digests_to_bytes(hh, hl)


def test_variable_lengths_and_padding_match_hashlib():
    # exercises: empty payload, sub-block, exact-block, multi-block items;
    # batch of 5 padded up to the 1024-item kernel tile
    payloads = [b"", b"a" * 7, b"b" * 128, b"c" * 129, bytes(range(256))]
    assert _run(payloads, nblocks=4) == [
        hashlib.blake2b(p, digest_size=32).digest() for p in payloads
    ]


def test_multiblock_chaining():
    payloads = [b"\x5a" * 500, b"\xa5" * 512]
    assert _run(payloads) == [
        hashlib.blake2b(p, digest_size=32).digest() for p in payloads
    ]


def test_the_kernel_has_one_body_and_no_variant_flags():
    """What the chip runs is what every caller gets: beside its three
    arrays the jitted entry takes ``digest_size``, ``block_items`` and
    ``interpret`` alone (its static arguments), and the kernel body
    ``digest_size``, ``unroll`` and ``packed`` — a variant flag cannot
    come back unseen."""
    import inspect

    from dat_replication_protocol_tpu.ops import blake2b_pallas as b2p

    native = inspect.signature(b2p.blake2b_native.__wrapped__)
    assert list(native.parameters) == [
        "mh", "ml", "lengths", "digest_size", "block_items", "interpret"]
    kernel = inspect.signature(b2p._kernel)
    assert [n for n, p in kernel.parameters.items()
            if p.kind is p.KEYWORD_ONLY] == ["digest_size", "unroll", "packed"]


@pytest.mark.parametrize(
    "lens, nblocks",
    [([0, 1, 127, 128], 1), ([129, 255, 256, 0, 7], 2), ([512] * 3, 4)],
    ids=["one-block", "two-blocks", "full-slots"],
)
def test_words_entry_point_splits_and_matches_hashlib(lens, nblocks):
    import numpy as np

    from dat_replication_protocol_tpu.ops.blake2b import stage_payloads
    from dat_replication_protocol_tpu.ops.blake2b_pallas import (
        blake2b_words_pallas,
    )

    payloads = [bytes((i + 3 * k) & 0xFF for i in range(n))
                for k, n in enumerate(lens)]
    buf = np.full((8, nblocks * 128), 0x5A, dtype=np.uint8)   # stale bytes
    lengths = stage_payloads(payloads, buf)
    hh, hl = blake2b_words_pallas(
        jnp.asarray(buf.view("<u4")), jnp.asarray(lengths), interpret=True)
    assert digests_to_bytes(hh, hl)[: len(payloads)] == [
        hashlib.blake2b(p, digest_size=32).digest() for p in payloads
    ]


@pytest.mark.parametrize(
    "twin", ["blake2b_words_pallas", "blake2b_words_pallas_donated"])
def test_words_program_keeps_the_prefix_the_trace_reader_finds(twin):
    """benchmarks/layer_metrics/blake2b_hbm_share.py sums the device time
    of the programs named ``jit_blake2b*``: split, transposes and kernel
    stay ONE such program."""
    import jax

    from dat_replication_protocol_tpu.ops import blake2b_pallas

    lowered = getattr(blake2b_pallas, twin)._fn.lower(
        jax.ShapeDtypeStruct((8, 32), jnp.uint32),
        jax.ShapeDtypeStruct((8,), jnp.uint32), interpret=True)
    assert "module @jit_blake2b_words_pallas" in lowered.as_text()


# -- tiles under 1,024 items: one packed tile, no padding to a full one -----

@pytest.mark.parametrize(
    "items, tile, packed",
    [(1, 32, (1, 32)), (32, 32, (1, 32)), (33, 64, (1, 64)),
     (128, 128, (1, 128)), (129, 256, (2, 128)), (512, 512, (4, 128)),
     (513, 1024, None), (1024, 1024, None), (5000, 1024, None)],
    ids=lambda v: str(v),
)
def test_a_batch_under_one_tile_is_its_own_packed_tile(items, tile, packed):
    from dat_replication_protocol_tpu.ops.blake2b_pallas import (
        _packed_shape,
        tile_items,
        to_native,
    )

    assert tile_items(items) == tile
    mh = jnp.zeros((items, 2, 16), jnp.uint32)
    mh_n, ml_n, len_n, B = to_native(mh, mh, jnp.zeros((items,), jnp.uint32))
    assert B == items and ml_n.shape == mh_n.shape
    if packed is None:
        rows = -(-items // 1024) * 1024
        assert mh_n.shape == (2, 16, 8, rows // 8)
        assert len_n.shape == (8, rows // 8)
    else:
        assert _packed_shape(tile) == packed == len_n.shape
        assert mh_n.shape == (2, 16 * packed[0], packed[1])


@pytest.mark.parametrize("items", [1, 32, 33, 100, 128, 200, 256, 300, 512])
def test_each_packed_tile_matches_hashlib(items):
    """Every tile width under 1,024 rows — lanes 32, 64, 128 and 1, 2, 4
    sublanes — on lengths that end in every place a block can."""
    edges = [0, 1, 127, 128, 129, 255, 256, 257, 383, 384]
    payloads = [bytes((7 * i + k) & 0xFF for k in range(edges[i % 10]))
                for i in range(items)]
    assert _run(payloads, nblocks=4) == [
        hashlib.blake2b(p, digest_size=32).digest() for p in payloads
    ]


def test_native_refuses_words_that_do_not_hold_their_lengths():
    from dat_replication_protocol_tpu.ops.blake2b_pallas import blake2b_native

    with pytest.raises(ValueError, match="do not hold lengths"):
        blake2b_native(jnp.zeros((2, 16 * 2, 128), jnp.uint32),
                       jnp.zeros((2, 16 * 2, 128), jnp.uint32),
                       jnp.zeros((1, 128), jnp.uint32), interpret=True)
