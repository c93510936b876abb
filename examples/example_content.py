"""Content-addressed blob sync: ship only the chunks an edit touched.

The dat workflow the wire protocol exists to serve (reference:
README.md:73 — blobs stream as content-addressed pieces): CDC chunk a
blob on device, BLAKE2b every chunk in batched dispatches, fold a Merkle
root, and after an edit exchange only the chunks the other side lacks.

Run: JAX_PLATFORMS=cpu python examples/example_content.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from dat_replication_protocol_tpu.utils.cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

from dat_replication_protocol_tpu.runtime import (  # noqa: E402
    content_address,
    delta,
    reassemble,
)


def main() -> None:
    rng = np.random.default_rng(0)
    v1 = rng.integers(0, 256, 1 << 18, dtype=np.uint8).tobytes()
    v2 = v1[:5000] + b"--edited--" + v1[5000:]  # insert near the front

    a = content_address(v1, avg_bits=10)
    b = content_address(v2, avg_bits=10)
    print(f"v1: {a.nchunks} chunks, root {a.root.hex()[:16]}…")
    print(f"v2: {b.nchunks} chunks, root {b.root.hex()[:16]}…")

    need = delta(a, b)
    offs, lens = b.extents()
    sent = {i: v2[int(offs[i]):int(offs[i]) + int(lens[i])] for i in need}
    moved = sum(len(p) for p in sent.values())
    print(
        f"delta: {len(need)}/{b.nchunks} chunks, {moved} bytes "
        f"({100 * moved / len(v2):.1f}% of the blob)"
    )
    assert reassemble(b, v1, a, sent) == v2
    print("receiver reassembled v2 from v1 + delta, digests verified")


if __name__ == "__main__":
    main()
