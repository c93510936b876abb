"""Engine routing: device batches when an accelerator backs jax, host
engines otherwise — "batch or stay home" (DESIGN.md §2 rule 0).

Host routing is a choice the code OBSERVES, never a fallback: it is
taken for an explicit ``0`` override, for a CPU platform (configured,
or the one jax initialised), or where jax is not installed.  A backend
that fails to initialise raises out of here — a sidecar that lost its
chip must say so, not answer from hashlib.

The configured platform string is consulted first because it decides
without initialising any backend (host-only processes stay jax-cold);
only when nothing is configured is the initialised backend asked.
"""

from __future__ import annotations

import os
from typing import Optional

# the host reasons under which jax is nevertheless in play (a CPU
# backend): records that name an engine describe the device for these
CPU_CONFIGURED = "cpu platform configured"
CPU_BACKEND = "cpu backend"


def host_reason(force_env: str) -> Optional[str]:
    """Why host engines take batch work on this host, or None when the
    device takes it.

    ``force_env`` names an override variable: ``"1"`` forces the device
    path, ``"0"`` forces the host path (tests / experiments).
    """
    force = os.environ.get(force_env)
    if force == "0":
        return f"{force_env}=0"
    if force == "1":
        return None
    try:
        import jax  # noqa: PLC0415
    except ImportError:
        return "jax not importable"
    cfg = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS")
    if cfg:
        if cfg.split(",")[0].strip().lower() == "cpu":
            return CPU_CONFIGURED
        return None
    # nothing configured: ask the backend jax initialises (a failed
    # initialisation raises — there is no host answer to hide it behind)
    return CPU_BACKEND if jax.default_backend() == "cpu" else None


def prefer_host(force_env: str) -> bool:
    """True when host engines should take batch work on this host (see
    :func:`host_reason`)."""
    return host_reason(force_env) is not None


def describe_device() -> dict:
    """The backend jax runs on here, as jax reports it — initialises it.
    Every record that names an engine carries these three fields."""
    import jax  # noqa: PLC0415

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }
