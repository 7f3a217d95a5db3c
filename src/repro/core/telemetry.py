"""Spans and counters of the replay path, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: while a JAX profiler
trace runs, it lands on the host plane of the same ``.xplane.pb`` as the
device's ``XLA Ops``, on one nanosecond clock, with its keyword
arguments as event stats. With no trace running it records nothing and
costs about a microsecond. This module never imports jax: where no one
else has loaded it, as in a numpy-only deployment, a span is a shared
no-op context.

Counters are process-wide monotonic integers; a caller reads them with
:func:`counters` before and after the work it measures.

Garbage collections are spans too (``py.gc``, stat ``generation``), so
that a gap in the device trace can be put down to one.
"""
from __future__ import annotations

import contextlib
import gc
import sys
from typing import Dict, Optional

_NO_SPAN = contextlib.nullcontext()
#: ``jax.profiler.TraceAnnotation``, once jax has been loaded
_annotation = None
_counters: Dict[str, int] = {}
#: the span of the collection in progress (collections never overlap)
_gc_span: Optional[object] = None


def _trace_annotation():
    global _annotation
    if _annotation is None:
        # jax.profiler is in sys.modules from early in jax's own import,
        # before it defines the class; wait until it has
        profiler = sys.modules.get("jax.profiler")
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def span(name: str, **stats):
    """A context that records ``name`` with ``stats`` while a profiler
    trace runs."""
    annotation = _trace_annotation()
    if annotation is None:
        return _NO_SPAN
    return annotation(name, **stats)


def count(name: str, n: int = 1) -> None:
    _counters[name] = _counters.get(name, 0) + n


def counters() -> Dict[str, int]:
    """A copy of every counter's value."""
    return dict(_counters)


def _on_gc(phase: str, info: Dict) -> None:
    global _gc_span
    if phase == "start":
        annotation = _trace_annotation()
        if annotation is not None:
            _gc_span = annotation("py.gc", generation=info["generation"])
            _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


gc.callbacks.append(_on_gc)
