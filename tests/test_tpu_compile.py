"""The served BLAKE2b programs, compiled for a v5e that is described and
not attached (on-chip-measurement guide §2.3): Mosaic accepts every
declared tile — interpret mode cannot say that — and a few wide rows do
not turn into a gigabyte of padded temporaries.  Nothing runs; a compile
that passes is not a chip run.

One file, fixtures local to it: only the worker that is given this file
loads the TPU's compiler.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dat_replication_protocol_tpu.ops import blake2b as b2
from dat_replication_protocol_tpu.ops import blake2b_pallas as b2p

MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu out
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without
    # one: keep these out of the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(topo.devices), ("data",))


def _compiled(one_chip, rows, nblocks):
    fn = b2p.blake2b_words_pallas.__wrapped__
    words = jax.ShapeDtypeStruct((rows, nblocks * 32), jnp.uint32,
                                 sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((rows,), jnp.uint32, sharding=one_chip)
    return fn.lower(words, lengths, 32).compile()


@pytest.mark.parametrize(
    "nblocks, rows",
    [(16, 1024)] + [(8192, r) for r in b2.declared_rows(8192)]
    + [(65536, 32)],
    ids=lambda v: str(v),
)
def test_each_declared_shape_compiles_for_the_v5e(one_chip, nblocks, rows):
    assert rows in b2.declared_rows(nblocks)
    compiled = _compiled(one_chip, rows, nblocks)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    staged = rows * nblocks * 128
    assert mem.argument_size_in_bytes < staged + MIB
    # the kernel reads the staged rows where they lie: no copy of them
    # (no split halves, no transposes) among the program's temporaries
    assert mem.temp_size_in_bytes <= staged // 8 + 4 * MIB


@pytest.mark.parametrize("nblocks, per_chip", [(8192, 32), (8192, 64),
                                               (16, 1024)],
                         ids=lambda v: str(v))
def test_the_sharded_program_compiles_for_a_four_chip_host(four_chips,
                                                           nblocks,
                                                           per_chip):
    """ISSUE 35: the mesh hub's program — the served Pallas words
    program under `shard_map` — is partitioned by the v5e compiler: each
    chip gets its shard of the rows and the kernel, nothing
    is exchanged between chips, and no scan over the message blocks is
    left in it."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    assert per_chip in b2.declared_rows(nblocks)
    rows = four_chips.devices.size * per_chip
    by_rows = NamedSharding(four_chips, P("data"))
    fn = b2._sharded_words_program(four_chips, True, True, 32).__wrapped__
    compiled = fn.lower(
        jax.ShapeDtypeStruct((rows, nblocks * 32), jnp.uint32,
                             sharding=by_rows),
        jax.ShapeDtypeStruct((rows,), jnp.uint32, sharding=by_rows),
    ).compile()
    text = compiled.as_text()
    head = text.splitlines()[0]
    assert head.startswith("HloModule jit_mesh_blake2b_words")
    assert f"u32[{per_chip},{nblocks * 32}]" in head   # a chip's shard
    assert "tpu_custom_call" in text
    for op in ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter", " while("):
        assert op not in text, op
    mem = compiled.memory_analysis()      # bytes on each device
    staged = per_chip * nblocks * 128
    assert mem.argument_size_in_bytes < staged + MIB
    assert mem.temp_size_in_bytes <= staged // 8 + 4 * MIB
