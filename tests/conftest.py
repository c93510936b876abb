"""Test env: force a virtual 8-device CPU mesh before JAX initializes.

Tests never need an accelerator: sharding tests run against
``--xla_force_host_platform_device_count=8`` on the CPU backend, which
exercises the same mesh/collective code paths XLA uses on real ICI, and
every Pallas kernel runs with ``interpret=True``.  This must *override*
(not just default) ``JAX_PLATFORMS``: a host with a chip would otherwise
initialize it, and one chip cannot host the 8-way mesh tests.  The chip
itself is exercised by ``chip_smoke.py``, not by this suite.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# persistent compile cache: the CPU backend's scanned-BLAKE2b/tree
# programs take minutes to compile cold
sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
from dat_replication_protocol_tpu.utils.cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


# -- shared telemetry isolation ---------------------------------------------

import pytest  # noqa: E402


@pytest.fixture
def obs_enabled():
    """Enable the obs gate for one test with clean metric values, an
    empty event ring, an empty span ring, a disarmed flight recorder,
    and a reset device sentinel, restoring the prior gate state
    afterwards — all five are process-global, so isolation is
    explicit."""
    from dat_replication_protocol_tpu.obs import device, events, flight, \
        metrics, propagation, tracing, watermarks, wirecost

    was_on = metrics.OBS.on
    metrics.REGISTRY.reset()
    events.EVENTS.clear()
    tracing.SPANS.clear()
    flight.FLIGHT._reset_for_tests()
    device.SENTINEL.reset_for_tests()
    device.BUCKETS.reset_for_tests()
    device.reset_engine_notes()
    watermarks.WATERMARKS.reset_for_tests()
    propagation.PROPAGATION.reset_for_tests()
    wirecost.WIRECOST.reset_for_tests()
    metrics.enable()
    try:
        yield metrics
    finally:
        metrics.OBS.on = was_on
        metrics.REGISTRY.reset()
        events.EVENTS.clear()
        events.EVENTS.detach_sink()
        tracing.SPANS.clear()
        tracing.SPANS.detach_sink()
        flight.FLIGHT._reset_for_tests()
        device.SENTINEL.reset_for_tests()
        device.reset_engine_notes()
        watermarks.WATERMARKS.reset_for_tests()
        propagation.PROPAGATION.reset_for_tests()
        wirecost.WIRECOST.reset_for_tests()
