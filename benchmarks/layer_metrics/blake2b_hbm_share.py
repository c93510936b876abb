"""blake2b_hbm_share (%): how near the BLAKE2b programs run to the HBM
BANDWIDTH bound, and only that bound.

    payload bytes hashed in the slice / peak HBM bytes/s
    -----------------------------------------------------
    device seconds of the `jit_blake2b*` programs in the slice

BLAKE2b on this chip is bound by 32-bit vector integer operations, for
which no v5e peak is published: the compute bound, and so the kernel's
true roofline share, is an open question (PERF.md), not a guessed
constant.  This number says how far the kernel is from being
memory-bound; it cannot pass 100%.

Bytes: what the algorithm must read — the payloads, not the padded
staging.  The slice's program runs times the mean payload bytes of a
dispatch over the window (`blake2b_buckets` items x the clients' mean
item payload / dispatches).  Kernels are found by program name; see
reduce_trace.py."""

import _stats

PROGRAMS = "jit_blake2b"


def read(ctx):
    trace = ctx.get("trace")
    rows = _stats.bucket_deltas(ctx)
    mean = _stats.mean_payload_bytes(ctx)
    if not trace or rows is None or mean is None:
        return None
    runs = [v for k, v in trace["programs"].items()
            if k.startswith(PROGRAMS)]
    seconds, count = sum(r[0] for r in runs), sum(r[1] for r in runs)
    if seconds <= 0:
        return None
    per_dispatch = mean * sum(r["items"] for r in rows.values()) \
        / sum(r["dispatches"] for r in rows.values())
    least = count * per_dispatch / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
