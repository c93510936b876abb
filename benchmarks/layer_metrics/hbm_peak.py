"""hbm_peak (GiB): `device.mem.peak_bytes_in_use` in the snapshot at
the window's end — the process's peak so far, warm-up included."""


def read(ctx):
    snaps = ctx.get("snaps")
    if not snaps or snaps[1] is None:
        return None
    v = snaps[1]["metrics"]["gauges"].get("device.mem.peak_bytes_in_use")
    return None if v is None else v / (1 << 30)
