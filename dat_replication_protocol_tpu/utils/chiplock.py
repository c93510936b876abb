"""Exclusive chip mutex shared by ``bench.py`` and every diagnostic script.

Round-4 lesson: the round's only pipelined full-bench hash capture
recorded 22.76 GiB/s because an ad-hoc diagnostic ran concurrently on
the same chip — the uncontended rate (37.9–39.1 GiB/s) was measured
separately, and the one driver-shaped artifact carried the polluted
number.  Nothing coordinated the two processes.

This module is that coordination: one ``flock(2)``-style mutex that
every device-touching entry point (the bench harness and the experiment
scripts) takes before initializing the backend.  flock is released by
the kernel when the holder dies, so a crashed diagnostic can never
leave the chip wedged-locked; no stale-lock sweeper is needed.

Artifact contract: device legs record ``uncontended: bool`` — True iff
this process acquired the lock *without waiting* and held it for the
whole leg.  A wait means another cooperating process was just on the
chip (its queues/clocks may not have drained); running lockless after
``max_wait`` expires records False, never silence.

The lock scopes a *chip*, not a repo: the default path lives in /tmp so
two checkouts driving the same device still exclude each other.  Override with ``DAT_CHIP_LOCK`` (e.g. per-device paths on a
multi-chip host).
"""

from __future__ import annotations

import errno
import fcntl
import os
import time
from contextlib import contextmanager

from ..obs.metrics import OBS as _OBS
from ..obs.metrics import counter as _counter
from ..obs.metrics import histogram as _histogram

# chip-mutex contention telemetry (device-telemetry catalog): every
# acquisition's wait lands in the histogram, so `bench --metrics`
# artifacts carry the contention story from the registry instead of
# only the ad-hoc per-leg `waited_s` field
_M_WAIT = _histogram("device.chiplock.wait")
_M_ACQUIRES = _counter("device.chiplock.acquires")
_M_CONTENDED = _counter("device.chiplock.contended")
_M_LOCKLESS = _counter("device.chiplock.lockless")

DEFAULT_LOCK_PATH = "/tmp/dat_tpu_chip.lock"


def lock_path() -> str:
    return os.environ.get("DAT_CHIP_LOCK", DEFAULT_LOCK_PATH)


class ChipLease:
    """What ``chip_lock`` yields: did we get it, and did we have to wait."""

    def __init__(self, held: bool, waited_s: float, path: str) -> None:
        self.held = held
        self.waited_s = waited_s
        self.path = path

    @property
    def uncontended(self) -> bool:
        """True iff the chip was free the moment we asked for it."""
        return self.held and self.waited_s == 0.0

    def as_fields(self) -> dict:
        """The artifact-record form (merged into device-leg results).

        When the lock IS held, the flock itself certifies the whole leg
        (no cooperating peer can run until release) so the values frozen
        at acquisition stay valid.  When it is NOT held (ran lockless
        after ``max_wait``), acquisition-time state says nothing about
        now — re-probe so each config's record reflects contention at
        the moment it was stamped.
        """
        contended_now = False
        if not self.held:
            try:
                fd = os.open(self.path, os.O_CREAT | os.O_RDWR, 0o666)
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    fcntl.flock(fd, fcntl.LOCK_UN)
                except OSError:
                    contended_now = True
                finally:
                    os.close(fd)
            except OSError:
                pass
        return {
            "uncontended": self.uncontended and not contended_now,
            "chip_lock": {
                "held": self.held,
                "waited_s": round(self.waited_s, 1),
                **({"peer_active": contended_now} if not self.held else {}),
            },
        }


@contextmanager
def chip_lock(max_wait: float | None = None, poll_s: float = 2.0):
    """Hold the exclusive chip mutex for the duration of the block.

    * acquired immediately  -> lease.uncontended is True;
    * acquired after a wait -> held=True, uncontended=False;
    * still contended after ``max_wait`` seconds -> the block runs
      WITHOUT the lock (held=False) so a stuck peer cannot blank a
      bench run — the artifact just says so.  ``max_wait=None`` waits
      forever (the right mode for diagnostics, which have no deadline
      and must never run concurrently with a capture).
    """
    path = lock_path()
    try:
        fd = os.open(path, os.O_CREAT | os.O_RDWR, 0o666)
    except OSError:
        # e.g. the lock file belongs to another user (umask strips the
        # 0o666): degrade to lockless-with-a-record rather than blank
        # the run this lock exists to protect
        if _OBS.on:
            _M_LOCKLESS.inc()
        yield ChipLease(False, 0.0, path)
        return
    held = False
    waited = 0.0
    try:
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            held = True
        except OSError as e:
            if e.errno not in (errno.EAGAIN, errno.EACCES):
                raise
            t0 = time.monotonic()
            while True:
                if max_wait is not None and time.monotonic() - t0 >= max_wait:
                    break
                time.sleep(poll_s if max_wait is None
                           else min(poll_s, max_wait / 10 + 0.01))
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    held = True
                    break
                except OSError as e2:
                    if e2.errno not in (errno.EAGAIN, errno.EACCES):
                        raise
            waited = time.monotonic() - t0
        if _OBS.on:
            _M_WAIT.observe(waited)
            if held:
                _M_ACQUIRES.inc()
            else:
                _M_LOCKLESS.inc()
            if waited > 0.0:
                _M_CONTENDED.inc()
        if held:
            # best-effort breadcrumb for a human inspecting a contended
            # window; failures (read-only fs) must not break the lock
            try:
                os.ftruncate(fd, 0)
                os.write(fd, f"pid={os.getpid()}\n".encode())
            except OSError:
                pass
        yield ChipLease(held, waited, path)
    finally:
        try:
            if held:
                fcntl.flock(fd, fcntl.LOCK_UN)
        finally:
            os.close(fd)
