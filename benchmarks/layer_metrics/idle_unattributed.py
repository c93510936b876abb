"""idle_unattributed (%): the share of the device's idle time in the
profiler slice that no host stage span covered — the `no span` row of
the reduction's `idle_gaps` over (slice − device busy).  0 where every
idle second has a name."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    idle_s = trace["window_s"] - trace["busy_s"]
    if idle_s <= 0:
        return None
    return 100.0 * dict(trace["idle_gaps"]).get("no span", 0.0) / idle_s
