"""mesh_hbm_share (%): how near the SHARDED BLAKE2b programs run to the
HBM bandwidth bound of the chips they run on, per chip — the four-chip
twin of `blake2b_hbm_share`, and like it the bandwidth bound only.

    payload bytes hashed in the slice / (devices x peak HBM bytes/s)
    ----------------------------------------------------------------
    device seconds of the `jit_mesh_blake2b*` programs in the slice

A `shard_map` program runs as long on every chip of its mesh and each
chip reads its share of the rows, so the least time the mesh could take
is the bytes over `devices` chips' bandwidth; the seconds are the first
device's (`XLA Modules`, see reduce_trace.py).  `devices` is the gauge
`hub.mesh.devices` of the snapshot at the window's end.  It cannot pass
100%.

Bytes: what the algorithm must read — the payloads, not the padded
staging — counted as `blake2b_hbm_share` counts them: the slice's
program runs times the mean payload bytes of a dispatch over the window
(`blake2b_buckets` items x the clients' mean item payload / dispatches).

None where the program has no such gauge or ran no such program (a
sidecar without `--hub-mesh`, a program older than the sharded engine):
the metric is then left out of the line."""

import _stats

PROGRAMS = "jit_mesh_blake2b"
GAUGE = "hub.mesh.devices"


def read(ctx):
    trace = ctx.get("trace")
    rows = _stats.bucket_deltas(ctx)
    if not trace or rows is None:
        return None
    mean = _stats.mean_payload_bytes(ctx)
    devices = ctx["snaps"][1]["metrics"]["gauges"].get(GAUGE)
    if mean is None or not devices:
        return None
    runs = [v for k, v in trace["programs"].items()
            if k.startswith(PROGRAMS)]
    seconds, count = sum(r[0] for r in runs), sum(r[1] for r in runs)
    if seconds <= 0:
        return None
    per_dispatch = mean * sum(r["items"] for r in rows.values()) \
        / sum(r["dispatches"] for r in rows.values())
    least = count * per_dispatch \
        / (devices * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
