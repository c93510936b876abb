"""Device experiment: settle the 50 GiB/s BLAKE2b question with DATA.

The ceiling analysis ("Mosaic scheduling of long dependent chains binds
at ~45% issue efficiency", ROADMAP S6) rests on elimination — 16
variants within noise — not observation.  This script runs the two
prescribed observations on an uncontended chip:

1. **Chain-length roofline sweep** (``--observe``): constant 2 GiB per
   dispatch, item size swept 128 KiB -> 2 MiB (the kernel's 1024-item
   tile floor caps the top), so the per-item dependent chain varies 16x
   (1024 -> 16384 blocks) while the batch (independent streams) varies
   16x the other way.  Total work is identical at every point.
     * flat curve  -> the bound is per-block issue rate; chain length /
       stream count don't matter, scheduling is NOT the binder at tile
       granularity, and 50 GiB/s needs a different inner loop;
     * rising as chains shorten -> scheduling IS the binder and the
       curve says how much a restructured kernel could recover.
2. **blocks_per_step amortization** at the best sweep point (1/2/4):
   whether per-block prologue/epilogue overhead is a material term.

Every rep is pipeline-fenced (depth 2); the chip flock guarantees no
concurrent diagnostic contaminates it.  One process per chip: run it
alone (``python _bps_experiment.py`` through the chip tool).

Output: one JSON line per measurement plus a final summary JSON line.
"""
import json
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from bench import _timed_reps_pipelined  # the unit-tested fencing helper
from dat_replication_protocol_tpu.ops.blake2b_pallas import blake2b_native
from dat_replication_protocol_tpu.utils.cache import enable_compile_cache
from dat_replication_protocol_tpu.utils.chiplock import chip_lock


def _measure(mh, ml, lens, chunk, item_bytes, reps=4, **kw):
    """Median pipelined-fenced GiB/s over ``reps`` (depth-2 in flight)."""
    run = lambda: blake2b_native(mh, ml, lens, **kw)  # noqa: E731
    fence = lambda o: (np.asarray(o[0][:1, :1]),      # noqa: E731
                       np.asarray(o[1][:1, :1]))
    fence(run())  # compile + warm
    dts = _timed_reps_pipelined(run, fence, reps, depth=2)
    g = chunk * item_bytes / statistics.median(dts) / (1 << 30)
    return g, dts


def observe():
    out = {"experiment": "blake2b_chain_length_roofline", "points": []}
    DISPATCH_BYTES = 1 << 31  # 2 GiB per dispatch at every sweep point
    kh, kl = jax.random.split(jax.random.PRNGKey(0))
    # (item_KiB) sweep; chunk = DISPATCH_BYTES / item.  Capped at
    # 2 MiB items: at 4 MiB chunk would drop to 512, under the kernel's
    # 1024-item tile floor (B/8 must be a multiple of the 128-lane
    # tile).  Chain still varies 16x across the sweep.
    for item_kib in (128, 256, 512, 1024, 2048):
        item_bytes = item_kib << 10
        nblocks = item_bytes // 128
        chunk = DISPATCH_BYTES // item_bytes
        shape = (nblocks, 16, 8, chunk // 8)
        mh = ml = lens = None
        try:
            mh = jax.random.bits(kh, shape, dtype=jnp.uint32)
            ml = jax.random.bits(kl, shape, dtype=jnp.uint32)
            lens = jnp.full((8, chunk // 8), item_bytes, dtype=jnp.uint32)
            jax.block_until_ready((mh, ml))
            g, dts = _measure(mh, ml, lens, chunk, item_bytes,
                              msg_loads=True)
        except Exception as e:  # one bad point must not kill the sweep
            print(json.dumps({"item_bytes": item_bytes,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
            continue
        finally:
            # release the 2 GiB of HBM even when the point fails — a
            # leaked pair would cascade OOM into every later point
            del mh, ml, lens
        pt = {"item_bytes": item_bytes, "chain_blocks": nblocks,
              "streams": chunk, "gib_s": round(g, 2),
              "rep_s": [round(d, 4) for d in dts]}
        print(json.dumps(pt), flush=True)
        out["points"].append(pt)

    # interpretation from the data itself
    if not out["points"]:
        out["verdict"] = "no sweep point completed"
        print(json.dumps(out), flush=True)
        return out
    gs = [p["gib_s"] for p in out["points"]]
    spread = (max(gs) - min(gs)) / max(gs)
    out["spread_frac"] = round(spread, 3)
    out["verdict"] = (
        "chain-length-sensitive: scheduling binds; shortest chains fastest"
        if spread > 0.15 and gs[0] == max(gs) else
        "flat (<15% spread): per-block issue-rate bound, chain length "
        "and stream count immaterial at tile granularity"
        if spread <= 0.15 else
        "non-monotonic: neither pure issue-rate nor chain-schedule bound"
    )

    # blocks_per_step amortization at the best point
    best = max(out["points"], key=lambda p: p["gib_s"])
    item_bytes, chunk = best["item_bytes"], best["streams"]
    nblocks = item_bytes // 128
    shape = (nblocks, 16, 8, chunk // 8)
    mh = jax.random.bits(kh, shape, dtype=jnp.uint32)
    ml = jax.random.bits(kl, shape, dtype=jnp.uint32)
    lens = jnp.full((8, chunk // 8), item_bytes, dtype=jnp.uint32)
    jax.block_until_ready((mh, ml))
    out["bps_at_best"] = {}
    for bps in (1, 2, 4):
        g, _ = _measure(mh, ml, lens, chunk, item_bytes,
                        msg_loads=True, blocks_per_step=bps)
        out["bps_at_best"][str(bps)] = round(g, 2)
        print(json.dumps({"bps": bps, "item_bytes": item_bytes,
                          "gib_s": round(g, 2)}), flush=True)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    # never run concurrently with a bench capture: block until the chip
    # is free (diagnostics have no deadline; captures do)
    with chip_lock() as lease:
        print(json.dumps({"chip_lock": lease.as_fields()}), flush=True)
        observe()
