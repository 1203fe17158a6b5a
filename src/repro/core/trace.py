"""Spans and instants on the profiler's clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler session runs it lands on the host plane of the same xplane as
the device's ``XLA Ops``, with ``ids`` as the event's stats; otherwise it
records nothing and costs about a microsecond. ``instant`` is a span of
no length. The program's names start with ``acan.``.

The control plane runs in processes that never import JAX (handler
workers, the tuple-space server). No profiler session can run in such a
process, so there a span is a no-op and JAX is not imported for it.
"""

from __future__ import annotations

import sys


class _Off:
    """A span in a process that has not loaded JAX."""

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **ids) -> None:
        """Ids known only once the span is open (as on TraceAnnotation)."""


_OFF = _Off()


def span(name: str, **ids: int | str):
    """A context manager that records ``name`` with ``ids`` around its
    body while a profiler session runs."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **ids)


def instant(name: str, **ids: int | str) -> None:
    """A zero-length span: an event at this moment."""
    with span(name, **ids):
        pass
