"""sidecar_cpu (cores): CPU seconds of the sidecar process, all its
threads, per wall second of the window (/proc/<pid>/stat, utime +
stime).  The host path as a whole: front end, pump, decoder, batching,
staging."""


def read(ctx):
    return ctx["cores"].get("sidecar")
