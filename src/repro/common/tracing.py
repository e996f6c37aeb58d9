"""The program's own tracing: device phase scopes, host spans, a compile
counter.  One system, the JAX profiler, carries all of it, so nothing is
recorded unless a trace is running (``jax.profiler.start_trace``), and the
host spans share the device trace's clock.

* ``phase(name)``: a ``jax.named_scope`` over one device phase of the
  round body; ``name`` is one of ``PHASES``.  The scope lands in each op's
  ``op_name`` metadata, so a trace (or the compiled HLO) says which phase
  an op belongs to.  Scopes add metadata only: the numbers are unchanged.
* ``span(name, **stats)``: a host span (``TraceAnnotation``).  Spans nest
  by time on their thread, which records the parent; keyword stats (and
  ``set_metadata`` on the entered span) ride on the span's event.
* ``compile_count()``: XLA compilations in this process since its first
  call, which registers the listener.

Spans stay in the profiler's memory and are written when the trace stops.
``README.md`` ("Tracing the engine") lists every span, scope and stat.
"""
from __future__ import annotations

import jax
from jax.profiler import TraceAnnotation

# device phases of FedAREngine._round_step, in round order
PHASES = (
    "faults",
    "select",
    "local_sgd",
    "latency",
    "codec.encode",
    "codec.decode",
    "quarantine",
    "deviation",
    "defense",
    "aggregate",
    "trust",
    "eval",
)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = None  # [count] once the listener is registered


def phase(name: str):
    """The named scope of device phase ``name`` (one of ``PHASES``)."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r} (known: {PHASES})")
    return jax.named_scope(name)


def span(name: str, **stats) -> TraceAnnotation:
    """A host span named ``name`` with numeric ``stats``; a no-op unless a
    trace is running."""
    return TraceAnnotation(name, **stats)


def enabled() -> bool:
    """Whether a trace is recording host spans: stats that cost work to
    compute are gated on it."""
    return TraceAnnotation.is_enabled()


def _on_event(event, _secs, **_):
    if event == COMPILE_EVENT:
        _compiles[0] += 1


def compile_count() -> int:
    """XLA compilations in this process (persistent-cache loads included)
    since the first call."""
    global _compiles
    if _compiles is None:
        _compiles = [0]
        jax.monitoring.register_event_duration_secs_listener(_on_event)
    return _compiles[0]
