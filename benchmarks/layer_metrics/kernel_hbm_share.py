"""kernel_hbm_share (%): how near the one-chip BLAKE2b programs run to
the HBM bandwidth bound — `blake2b_hbm_share`'s arithmetic with the
payload a program reads taken from the program's own count:

    payload bytes hashed in the slice / peak HBM bytes/s
    -----------------------------------------------------
    device seconds of the `jit_blake2b*` programs in the slice

the slice's program runs times the mean `payload_bytes` of a bucket
dispatch over the window (`blake2b_buckets`).  Where the clients send
blobs the host stream hashes (`plain.objects`), `blake2b_hbm_share`
counts them as read by the kernel and this one does not.  It cannot
pass 100%."""

import _kernel_bytes

PROGRAMS = "jit_blake2b"


def read(ctx):
    trace = ctx.get("trace")
    rows = _kernel_bytes.bucket_deltas(ctx)
    if not trace or rows is None:
        return None
    runs = [v for k, v in trace["programs"].items()
            if k.startswith(PROGRAMS)]
    seconds, count = sum(r[0] for r in runs), sum(r[1] for r in runs)
    if seconds <= 0:
        return None
    per_dispatch = sum(r["payload_bytes"] for r in rows.values()) \
        / sum(r["dispatches"] for r in rows.values())
    return 100.0 * count * per_dispatch / ctx["peaks"]["hbm_bytes_per_s"] \
        / seconds
