"""Program spans on the profiler's clock.

``span(name, **args)`` wraps ``jax.profiler.TraceAnnotation``: the profiler
records it only while a trace runs, into the same ``.xplane.pb`` and on the
same clock as the device's ops.  ``args`` (and later ``set(**args)``) go
through the annotation's own keyword arguments, which are formatted only
while a trace runs, and appear as the event's stats.

The open spans of each thread sit on a small stack, so that code which
runs inside them (a compile listener) can ask which span is innermost
(``innermost``), and which ``owner`` (an object that such code credits)
that span names.
"""
from __future__ import annotations

import threading

from jax.profiler import TraceAnnotation

_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Context manager: one named span, nested under the open ones."""

    __slots__ = ("_ann", "_entry")

    def __init__(self, name: str, owner=None, **args):
        self._entry = (name, owner)
        self._ann = TraceAnnotation(name, **args)

    def __enter__(self) -> "span":
        _stack().append(self._entry)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._ann.__exit__(*exc)
        _stack().pop()

    def set(self, **args) -> None:
        """Add stats to the open span (formatted only while tracing)."""
        self._ann.set_metadata(**args)


def innermost() -> tuple[str, object] | None:
    """``(name, owner)`` of this thread's innermost open span, or None."""
    stack = _stack()
    return stack[-1] if stack else None
