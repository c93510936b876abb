"""hub_parked_share (%): the hub's high-water mark of parked bytes
(`hub.parked.peak_bytes`: queued + in flight + undelivered, all
sessions) over its configured budget (`hub.parked.budget_bytes`), both
in the snapshot at the window's end — the process's peak so far, warm-up
included.  Past 50 a newcomer would be refused; past 100 a session is
shed.  None where the program has no such gauges (a sidecar without a
hub, a program older than the gauges)."""


def read(ctx):
    snaps = ctx.get("snaps")
    if not snaps or snaps[1] is None:
        return None
    gauges = snaps[1]["metrics"]["gauges"]
    peak = gauges.get("hub.parked.peak_bytes")
    budget = gauges.get("hub.parked.budget_bytes")
    if peak is None or not budget:
        return None
    return 100.0 * peak / budget
