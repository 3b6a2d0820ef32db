"""Host spans of the serving path, on the JAX profiler's clock.

`span(name)` opens a `jax.profiler.TraceAnnotation` while a profiler
session is collecting, and otherwise returns a shared no-op context: one
check, no name or attribute built.  The no-op is falsy, so attributes
are built only behind an `if`:

    with tracing.span("serve.prefill_chunk") as sp:
        if sp:
            sp.set_metadata(size=size, rows=len(slots))

Every name carries the `serve.` prefix (the benchmark's reduction names
device idle time by the innermost open one).  Spans wrap host Python
only; none is opened inside jitted code.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "serve."


class _Off:
    """The span used while no profiler collects: enters, exits, is falsy."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False


_OFF = _Off()
_collecting = TraceAnnotation.is_enabled


def span(name: str):
    """A TraceAnnotation named `name` while the profiler collects, else
    the shared no-op."""
    return TraceAnnotation(name) if _collecting() else _OFF
