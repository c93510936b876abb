"""loadgen_cpu (cores): CPU seconds of the client processes per wall
second of the window, all of them together (/proc/<pid>/stat, utime +
stime).  The guard on the yardstick itself: the report's earlier line
also names the busiest single process, and where that sits near a whole
core the cell is measuring the generator."""


def read(ctx):
    return sum(v for k, v in ctx["cores"].items() if k != "sidecar")
