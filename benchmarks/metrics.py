"""Metric arithmetic: from the clients' timings to the end-to-end numbers.

All times are `time.monotonic()` seconds as the client processes read
them; the window is [t0, t1].
"""

from __future__ import annotations

import math

import numpy as np

TAIL_Q = 95
TAIL_BEYOND = 10     # samples a percentile wants beyond it
# One batch's digests reach a client in two or three `recv`s
# milliseconds apart: at the host clock's honest resolution they are one
# arrival.  Without this the rest of the first batch counts as work done
# after the first edge (PR 24 read 271 MiB/s where 247 was true).
EDGE_RESOLUTION_S = 0.050


def percentile(values, q: float = TAIL_Q):
    """Nearest rank: the smallest value with at least q% of the samples
    at or under it.  None for no samples."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * v.size))
    return float(v[rank - 1])


def tail_warning(what: str, n: int, q: float = TAIL_Q) -> str | None:
    """A q-th percentile wants TAIL_BEYOND samples beyond it (200 for
    the 95th).  A run that holds fewer says so; it does not fail."""
    need = math.ceil(TAIL_BEYOND / (1.0 - q / 100.0))
    if n < need:
        return (f"{what}: {n} samples in the window, a p{q:g} wants "
                f"{need} ({TAIL_BEYOND} beyond it)")
    return None


def edge_to_edge(delivery_t, delivery_items, delivery_bytes, t0: float,
                 t1: float) -> dict:
    """Rates from the first reply arrival inside the window to the last,
    counting what arrived AFTER the first edge (arrivals within
    EDGE_RESOLUTION_S of it are the edge).  A cell whose digests come
    1,024 at a time every few seconds would be quantised by a tenth if
    whole deliveries were counted over the fixed window."""
    t = np.asarray(delivery_t, dtype=np.float64)
    order = np.argsort(t, kind="stable")
    t = t[order]
    items = np.asarray(delivery_items)[order]
    nbytes = np.asarray(delivery_bytes)[order]
    inside = (t >= t0) & (t <= t1)
    t, items, nbytes = t[inside], items[inside], nbytes[inside]
    out = {"deliveries": int(t.size), "items": int(items.sum()),
           "first_s": None, "last_s": None,
           "span_s": None, "items_per_s": None, "bytes_per_s": None}
    if not t.size:
        return out
    out.update(first_s=float(t[0] - t0), last_s=float(t[-1] - t0))
    after = t > t[0] + EDGE_RESOLUTION_S
    if not after.any():
        return out
    span = float(t[-1] - t[0])
    out.update(span_s=span,
               items_per_s=float(items[after].sum()) / span,
               bytes_per_s=float(nbytes[after].sum()) / span)
    return out


def in_window(t, values, t0: float, t1: float) -> np.ndarray:
    """The `values` whose time `t` fell inside the window."""
    t = np.asarray(t, dtype=np.float64)
    return np.asarray(values, dtype=np.float64)[(t >= t0) & (t <= t1)]


def end_to_end(timings: dict, t0: float, t1: float) -> tuple[dict, dict]:
    """(metrics by name, the figures for the earlier lines) from the
    clients' merged timings (`client.report`'s arrays)."""
    rates = edge_to_edge(timings["delivery_t"], timings["delivery_items"],
                         timings["delivery_bytes"], t0, t1)
    lags = in_window(timings["item_t"], timings["item_lag"], t0, t1) * 1e3
    sess = in_window(timings["session_t1"],
                     timings["session_t1"] - timings["session_t0"],
                     t0, t1) * 1e3
    metrics = {
        "payload_rate": None if rates["bytes_per_s"] is None
        else rates["bytes_per_s"] / (1 << 20),
        "digest_rate": rates["items_per_s"],
        "digest_lag_p95": percentile(lags),
        "session_p95": percentile(sess),
    }
    notes = {
        **rates,
        "digest_lag_p50_ms": percentile(lags, 50),
        "digest_lag_samples": int(lags.size),
        "session_p50_ms": percentile(sess, 50),
        "sessions_in_window": int(sess.size),
        "warnings": [w for w in (
            tail_warning("digest_lag_p95", int(lags.size)),
            tail_warning("session_p95", int(sess.size))
            if sess.size else None) if w],
    }
    return metrics, notes
