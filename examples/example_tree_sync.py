"""Remote diff via interactive Merkle descent, metered.

Two replicas hold versions of a blob.  Each content-addresses its copy
(CDC chunks + per-chunk digests), builds a Merkle tree over the chunk
digests, and the initiator walks both trees top-down with explicit wire
messages — locating the changed chunks in O(diff · log n) transferred
bytes, without either side shipping its chunk list.

Run: JAX_PLATFORMS=cpu python examples/example_tree_sync.py
"""

import random
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dat_replication_protocol_tpu.utils.cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

from dat_replication_protocol_tpu.ops import merkle  # noqa: E402
from dat_replication_protocol_tpu.runtime import (  # noqa: E402
    TreeSyncSession,
    content_address,
    tree_sync,
)


def _session(summary, width):
    # both replicas must pad to a SHARED width (chunk counts that
    # straddle a power-of-two boundary would otherwise build trees of
    # different heights and sync() rejects them); in a real deployment
    # the width rides with the root in the handshake
    import jax.numpy as jnp

    digs = [summary.digests[i].tobytes() for i in range(summary.nchunks)]
    hh, hl = merkle.digests_to_device(digs)
    pad = ((0, width - summary.nchunks), (0, 0))
    return TreeSyncSession(
        *merkle.build_tree(jnp.pad(hh, pad), jnp.pad(hl, pad))
    )


def main() -> None:
    rng = random.Random(7)
    v1 = rng.randbytes(1 << 18)
    v2 = bytearray(v1)
    v2[100_000:100_008] = b"CHANGED!"  # in-place edit, cuts unchanged
    s1 = content_address(v1, avg_bits=10)
    s2 = content_address(bytes(v2), avg_bits=10)
    print(f"replica A: {s1.nchunks} chunks; replica B: {s2.nchunks} chunks")

    from dat_replication_protocol_tpu.utils.num import next_pow2

    width = next_pow2(max(s1.nchunks, s2.nchunks))
    transcript = []
    diff = tree_sync(_session(s1, width), _session(s2, width), transcript)
    moved = sum(nb for _, nb in transcript)
    naive = s1.nchunks * 32
    print(
        f"descent found chunks {diff} changed in {len(transcript)} messages, "
        f"{moved} bytes (naive digest-list exchange: {naive} bytes)"
    )


if __name__ == "__main__":
    main()
