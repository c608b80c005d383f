"""Span timers and jax-profiler naming wrappers.

Three layers, all safe to leave in production call sites:

* ``span(name, **labels)`` — a named host region.  It always opens a
  ``jax.profiler.TraceAnnotation(name)``, so a captured trace shows the
  region on the device trace's clock (with no profiler running that is
  one inactive TraceMe check).  Only while telemetry is enabled does it
  read the clock: it records the elapsed seconds into the
  ``span_seconds`` histogram (label ``span=<name>`` plus any extras) and
  leaves them on the yielded ``Span``'s ``seconds``.

  ``span`` does NOT block on device work: callers that want the span to
  cover device execution must ``block_until_ready`` inside the span
  (the instrumented engines only do so when telemetry is enabled, so
  the disabled path keeps its async dispatch).

* ``annotate(name)`` — decorator naming a traced/jitted function in
  profiler output via ``jax.profiler.annotate_function``; identity
  when the profiler API is unavailable.

* ``named_scope(name)`` — re-export of ``jax.named_scope`` for naming
  *operations inside* a jitted program (the model's ``attn``, ``ffn``,
  ``lora`` and ``ce``, the stage programs' ``optimizer`` and
  ``aggregate``, the BGMV and quant-matmul kernels); metadata only,
  never changes the compiled computation.  ``scoped(name)`` is its
  decorator form, with a fresh scope per call.
"""
from __future__ import annotations

import contextlib
import functools
import time

try:  # pure-host fallback when no profiler is built in (CPU-only jax
    # still has these, but keep the subsystem importable without jax)
    from jax.profiler import TraceAnnotation as _TraceAnnotation
    from jax.profiler import annotate_function as _annotate_function
except Exception:  # pragma: no cover - exercised only on stripped jax
    _TraceAnnotation = None
    _annotate_function = None

try:
    from jax import named_scope
except Exception:  # pragma: no cover
    @contextlib.contextmanager
    def named_scope(name: str):
        yield


def annotate(name: str):
    """Decorator: name ``fn`` in profiler traces (identity w/o profiler)."""
    def deco(fn):
        if _annotate_function is None:
            return fn
        return _annotate_function(fn, name=name)
    return deco


def scoped(name: str):
    """Decorator: run ``fn`` under ``named_scope(name)``.  One scope is
    made per call: a ``jax.named_scope`` object used as the decorator
    itself keeps its saved name stack on the shared object, which two
    threads tracing at once would overwrite."""
    def deco(fn):
        @functools.wraps(fn)
        def call(*args, **kw):
            with named_scope(name):
                return fn(*args, **kw)
        return call
    return deco


class Span:
    """What ``span`` yields: ``seconds`` is the region's host wall time
    once it has closed, 0.0 while telemetry is off."""
    __slots__ = ("seconds",)

    def __init__(self):
        self.seconds = 0.0


@contextlib.contextmanager
def span(name: str, **labels):
    """Name a host region in profiler traces; with telemetry enabled,
    also time it into the ``span_seconds`` histogram."""
    import repro.obs as _obs  # late: repro.obs imports this module
    s = Span()
    ann = (_TraceAnnotation(name) if _TraceAnnotation is not None
           else contextlib.nullcontext())
    with ann:
        if not _obs.enabled():
            yield s
            return
        tel = _obs.active()
        t0 = time.perf_counter()
        try:
            yield s
        finally:
            s.seconds = time.perf_counter() - t0
            tel.metrics.histogram("span_seconds").observe(
                s.seconds, span=name, **labels)
