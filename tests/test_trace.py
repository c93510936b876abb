"""Tracing hooks: spans wrap work transparently."""

import jax.numpy as jnp
import numpy as np

from dat_replication_protocol_tpu.utils.trace import span


def test_span_is_transparent_and_reentrant():
    with span("outer"):
        with span("inner"):
            x = int(np.asarray(jnp.arange(8).sum()))
    assert x == 28


def test_span_factory_binds_once_and_is_cached():
    """ISSUE 3 satellite: span() must not re-attempt the jax.profiler
    import per call — the factory binds at first use (the
    _fastpath_gate trick) and every later span() call is one module
    attribute load plus the construction."""
    from dat_replication_protocol_tpu.utils import trace

    trace._reset_span_binding_for_tests()
    assert trace._span_factory is None
    with trace.span("bind-me"):
        pass
    bound = trace._span_factory
    assert bound is not None
    with trace.span("again"):
        pass
    assert trace._span_factory is bound  # cached, not re-derived


def test_span_falls_back_to_null_span_when_import_fails(monkeypatch):
    """With the import broken, the binding latches _NullSpan — and the
    cache means the broken import is attempted exactly once."""
    import builtins

    from dat_replication_protocol_tpu.utils import trace

    trace._reset_span_binding_for_tests()
    real_import = builtins.__import__
    calls = {"n": 0}

    def breaking_import(name, *a, **k):
        if name.startswith("jax"):
            calls["n"] += 1
            raise ImportError("jax unavailable in this process")
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", breaking_import)
    try:
        with trace.span("no-jax") as s:
            assert isinstance(s, trace._NullSpan)
        with trace.span("still-no-jax"):
            pass
        assert calls["n"] == 1  # bound once; second span pays no import
        assert trace._span_factory is trace._NullSpan
    finally:
        monkeypatch.undo()
        trace._reset_span_binding_for_tests()
