"""Pallas BLAKE2b kernel vs hashlib, via the interpreter on CPU.

The real Mosaic compile path is checked by ``tests/test_tpu_compile.py``
(compiled for a described chip) and runs on the TPU under
``chip_smoke.py`` and the benchmark; these tests check the kernel's
logic — the raw rows turned onto lanes in VMEM, state chaining across
blocks and grid steps, variable-length masks, batch padding — with
``interpret=True`` on small shapes.
"""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from dat_replication_protocol_tpu.ops.blake2b import (
    digests_to_bytes,
    pack_payloads,
    stage_payloads,
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


def _hashlib(payloads):
    return [hashlib.blake2b(p, digest_size=32).digest() for p in payloads]


def test_variable_lengths_and_padding_match_hashlib():
    # exercises: empty payload, sub-block, exact-block, multi-block items;
    # batch of 5 padded up to the kernel's smallest tile
    payloads = [b"", b"a" * 7, b"b" * 128, b"c" * 129, bytes(range(256))]
    assert _run(payloads, nblocks=4) == _hashlib(payloads)


def test_multiblock_chaining():
    payloads = [b"\x5a" * 500, b"\xa5" * 512]
    assert _run(payloads) == _hashlib(payloads)


def test_the_kernel_has_one_body_and_no_variant_flags():
    """What the chip runs is what every caller gets: beside its two
    arrays the jitted entry takes ``digest_size`` and ``interpret`` alone
    (its static arguments), the (mh, ml) entry the same beside its three,
    and the kernel body ``digest_size``, ``unroll`` and the two numbers
    of its tile's shape — a variant flag cannot come back unseen."""
    import inspect

    from dat_replication_protocol_tpu.ops import blake2b_pallas as b2p

    words = inspect.signature(b2p.blake2b_words_pallas._fn.__wrapped__)
    assert list(words.parameters) == [
        "words", "lengths", "digest_size", "interpret"]
    packed = inspect.signature(b2p.blake2b_packed_pallas)
    assert list(packed.parameters) == [
        "mh", "ml", "lengths", "digest_size", "interpret"]
    kernel = inspect.signature(b2p._kernel)
    assert [n for n, p in kernel.parameters.items()
            if p.kind is p.KEYWORD_ONLY] == [
        "digest_size", "unroll", "sublanes", "blocks"]


def test_the_packed_entry_interleaves_into_the_one_kernel(monkeypatch):
    """``blake2b_packed_pallas`` (the (mh, ml) callers) puts the halves
    back into the rows ``stage_payloads`` lays and hands them to the same
    kernel entry the served words program calls."""
    from dat_replication_protocol_tpu.ops import blake2b_pallas as b2p

    seen = []
    real = b2p._compress_rows

    def spy(words, lengths, digest_size, interpret):
        seen.append(np.asarray(words))
        return real(words, lengths, digest_size, interpret)

    monkeypatch.setattr(b2p, "_compress_rows", spy)
    payloads = [bytes((5 * i + k) & 0xFF for k in range(n))
                for i, n in enumerate([0, 1, 200, 256, 33])]
    assert _run(payloads, nblocks=2) == _hashlib(payloads)
    buf = np.empty((len(payloads), 256), np.uint8)
    stage_payloads(payloads, buf)
    (words,) = seen
    assert words.shape == (32, 64)          # padded to the smallest tile
    assert np.array_equal(words[:len(payloads)], buf.view("<u4"))
    assert not words[len(payloads):].any()


# every place a length can end in a block
_EDGES = (0, 1, 7, 8, 63, 64, 120, 127)


def _payloads(n_items: int, nblocks: int):
    """Lengths that end at every place in a block and in every block of
    the slot (so at every block of a multi-block grid step), one empty
    item and one full slot."""
    lens = [k * 128 + _EDGES[(i // nblocks) % len(_EDGES)]
            for i, k in zip(range(n_items), np.arange(n_items) % nblocks)]
    lens[0], lens[-1] = 0, nblocks * 128
    return [bytes((i + 3 * k) & 0xFF for i in range(n))
            for k, n in enumerate(lens)]


@pytest.mark.parametrize(
    "lens, nblocks",
    [([0, 1, 127, 128], 1), ([129, 255, 256, 0, 7], 2), ([512] * 3, 4)],
    ids=["one-block", "two-blocks", "full-slots"],
)
def test_words_entry_point_splits_and_matches_hashlib(lens, nblocks):
    from dat_replication_protocol_tpu.ops.blake2b_pallas import (
        blake2b_words_pallas,
    )

    payloads = [bytes((i + 3 * k) & 0xFF for i in range(n))
                for k, n in enumerate(lens)]
    buf = np.full((8, nblocks * 128), 0x5A, dtype=np.uint8)   # stale bytes
    lengths = stage_payloads(payloads, buf)
    hh, hl = blake2b_words_pallas(
        jnp.asarray(buf.view("<u4")), jnp.asarray(lengths), interpret=True)
    assert digests_to_bytes(hh, hl)[: len(payloads)] == _hashlib(payloads)


@pytest.mark.parametrize(
    "rows, nblocks, items",
    [(32, 1, 29), (64, 2, 61), (32, 3, 32), (128, 4, 100), (256, 4, 200),
     (512, 8, 300), (1024, 2, 1000), (2048, 4, 1100), (32, 64, 30),
     (32, 12, 17), (32, 6, 20)],
    ids=["lanes32-narrow-row", "lanes64-narrow-row", "three-blocks",
         "lanes128-one-step", "sublanes2", "sublanes4-eight-block-step",
         "full-tile", "two-tiles", "sixty-four-block-step",
         "twelve-blocks", "six-blocks-padded"],
)
def test_raw_rows_match_hashlib_on_every_staged_tile(rows, nblocks, items):
    """The served entry on each tile the staging lays: 32, 64 and 128
    lanes; 2, 4 and 8 sublanes; one and two full tiles; rows narrower
    than 128 lanes, whole-row steps and steps of 4 to 64 blocks, a slot
    the entry pads to whole steps — with stale bytes in the padding rows,
    which no item's digest may see."""
    from dat_replication_protocol_tpu.ops.blake2b_pallas import (
        blake2b_words_pallas,
    )

    payloads = _payloads(items, nblocks)
    buf = np.empty((rows, nblocks * 128), dtype=np.uint8)
    lengths = stage_payloads(payloads, buf)
    buf[items:] = 0x5A                                  # stale padding rows
    hh, hl = blake2b_words_pallas(
        jnp.asarray(buf.view("<u4")), jnp.asarray(lengths), interpret=True)
    assert hh.shape == hl.shape == (rows, 8)
    assert digests_to_bytes(hh, hl)[:items] == _hashlib(payloads)


@pytest.mark.parametrize(
    "twin", ["blake2b_words_pallas", "blake2b_words_pallas_donated"])
def test_words_program_keeps_the_prefix_the_trace_reader_finds(twin):
    """benchmarks/layer_metrics/blake2b_hbm_share.py sums the device time
    of the programs named ``jit_blake2b*``: the kernel and the reshapes
    around it stay ONE such program."""
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
    """``tile_shape`` picks the kernel's tile from the rows alone: a
    batch under 1,024 rows is ONE tile of itself (a power of two, at
    least 32) on ``S`` sublanes x ``L`` lanes, a larger one whole tiles
    of 8 x 128; the entry pads the rows to whole tiles and no further."""
    import jax

    from dat_replication_protocol_tpu.ops import blake2b_pallas as b2p

    shape = b2p.tile_shape(items, 2)
    assert shape.rows == tile
    assert (shape.sublanes, shape.lanes) == (packed or (8, 128))
    assert shape.blocks == 2                       # the whole 2-block row
    padded = -(-items // tile) * tile
    out = jax.eval_shape(
        lambda w, n: b2p._hash_rows(w, n, 32, True),
        jax.ShapeDtypeStruct((items, 64), jnp.uint32),
        jax.ShapeDtypeStruct((items,), jnp.uint32))
    assert [o.shape for o in out] == [(items, 8)] * 2
    inner = jax.make_jaxpr(lambda w, n: b2p._hash_rows(w, n, 32, True))(
        jax.ShapeDtypeStruct((items, 64), jnp.uint32),
        jax.ShapeDtypeStruct((items,), jnp.uint32))
    assert f"u32[{padded},64]" in str(inner)
    assert ("pad" in str(inner)) == (padded != items)


@pytest.mark.parametrize("items", [1, 32, 33, 100, 128, 200, 256, 300, 512])
def test_each_packed_tile_matches_hashlib(items):
    """Every tile width under 1,024 rows — lanes 32, 64, 128 and 1, 2, 4
    sublanes — on lengths that end in every place a block can."""
    edges = [0, 1, 127, 128, 129, 255, 256, 257, 383, 384]
    payloads = [bytes((7 * i + k) & 0xFF for k in range(edges[i % 10]))
                for i in range(items)]
    assert _run(payloads, nblocks=4) == _hashlib(payloads)


def test_native_refuses_words_that_do_not_hold_their_lengths():
    from dat_replication_protocol_tpu.ops.blake2b_pallas import (
        _compress_rows,
    )

    with pytest.raises(ValueError, match="do not hold lengths"):
        _compress_rows(jnp.zeros((64, 64), jnp.uint32),
                       jnp.zeros((32,), jnp.uint32), 32, True)
    with pytest.raises(ValueError, match="not whole tiles"):
        _compress_rows(jnp.zeros((48, 64), jnp.uint32),
                       jnp.zeros((48,), jnp.uint32), 32, True)
