"""Reconciling two divergent replicas with key-addressed sketches.

The reference delegates divergent-replica resume to dat core via the
Change.from/to version fields (reference: messages/schema.proto:4-5);
this framework reconciles in the data plane: each replica summarizes its
log into a key-addressed sketch on device, the sketches diff through the
Merkle tree, and only the records in differing cells are exchanged —
O(diff), independent of where inserts landed.

Run: JAX_PLATFORMS=cpu python examples/example_reconcile.py
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# repeat runs skip the multi-minute cold XLA compiles (CPU scanned path)
from dat_replication_protocol_tpu.utils.cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

from dat_replication_protocol_tpu.ops import reconcile  # noqa: E402


def main() -> None:
    keys_a = [b"row-%03d" % i for i in range(300)]
    records_a = [b"value-of:" + k for k in keys_a]

    # replica B diverged: an insert in the middle (misaligning every
    # later position), a delete, and a value flip
    keys_b = list(keys_a)
    records_b = list(records_a)
    keys_b.insert(140, b"row-new")
    records_b.insert(140, b"value-of:row-new")
    del keys_b[250], records_b[250]
    records_b[100] = records_b[100] + b"~updated"

    a = reconcile.LogSummary(records_a, keys_a, log2_slots=10)
    b = reconcile.LogSummary(records_b, keys_b, log2_slots=10)
    out = reconcile.reconcile(a, b)

    print(f"replica A: {len(keys_a)} records, B: {len(keys_b)} records")
    print(f"differing sketch cells: {len(out['slots'])}")
    print(f"A must send {len(out['a_keys'])} records: {out['a_keys'][:5]}...")
    print(f"B must send {len(out['b_keys'])} records: {out['b_keys'][:5]}...")


if __name__ == "__main__":
    main()
