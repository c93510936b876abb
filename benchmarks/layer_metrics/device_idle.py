"""device_idle (%): 1 - the union of the device-operation intervals over
the profiler slice's length (first device's `XLA Ops` line, between the
slice's two marks)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
