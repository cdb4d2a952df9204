"""Program spans: named host intervals with counters, written into the JAX
profiler's own trace.

``span(name, **counters)`` is a ``jax.profiler.TraceAnnotation``, so it is
recorded exactly while a profiler trace runs (``jax.profiler.start_trace``
to ``stop_trace``) and shares that trace's clock with the device's
operations.  The profiler keeps the events; nothing is held here.  Counters
known only once the work is done are added with ``set_metadata``::

    with span("repro.read") as s:
        arr, stats = ...
        s.set_metadata(bytes=stats.bytes_read)

JAX is looked up in ``sys.modules``, never imported: where no one has
imported it, no trace can be running, and a span is a null context.
"""

from __future__ import annotations

import sys

__all__ = ["span"]


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counters) -> None:
        pass


_NULL = _Null()


def span(name: str, **counters):
    """Context manager recording ``name`` with ``counters`` as its
    arguments while a profiler trace runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation(name, **counters)
