"""Host spans of the serving path, written into the JAX profiler's trace.

Each span is a ``jax.profiler.TraceAnnotation``: with no profiler running
it costs about a microsecond, and under ``jax.profiler.trace`` it lands on
the host plane on the same clock as the device's operations, so an idle
gap on the device can be put down to what the host was doing in it. The
names (``serve.*``, ``ditto.*``, ``diffusion.*``) are listed in
``docs/architecture.md`` ("Observability").

Spans opened inside :func:`dispatch` carry that dispatch's index as the
``dispatch`` argument, so every span of one ``ServeSession.serve`` call
shares its identifier without the index being threaded through the
serving functions' signatures.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

_DISPATCH: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_dispatch", default=None)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name``; inside :func:`dispatch` it also carries
    the dispatch index."""
    index = _DISPATCH.get()
    if index is not None:
        args["dispatch"] = index
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def dispatch(index: int, **args):
    """The ``serve.dispatch`` span of dispatch ``index``; spans opened
    inside it carry ``dispatch=index``."""
    token = _DISPATCH.set(index)
    try:
        with span("serve.dispatch", **args):
            yield
    finally:
        _DISPATCH.reset(token)
