"""Named spans at the engine's layer boundaries, on the profiler's clock.

``span(name, **ids)`` is a context manager around one unit of the
engine's work: a task, a chunk, a message batch, a request, a wait. While
tracing is off it returns one shared no-op, after a single check of a
module global: no allocation, no string formatting, no ``jax`` import.
While tracing is on it returns ``jax.profiler.TraceAnnotation(name,
**ids)``, so the span lands on the profiler's host plane, on the same
clock as the device's planes; the small integer ``ids`` (``job``,
``stage``, ``task``, ``attempt``, ``dispatch``, counts) become the
event's stats. ``set_metadata(**counts)`` adds stats known only when the
span ends.

A *held* span (``hold``/``release``) covers a stretch of per-record work,
such as the shuffle writer's, without one span per record: ``hold``
opens it unless one is already held on this thread, and the next
``span`` opened on the thread ends it first, so work pulled from
upstream between two records is never counted as the writer's. Inside
``keep_held()`` spans nest under the held span instead.

Names are constants; never open a span per record, row or key.
"""

from __future__ import annotations

import threading

_on = False
_local = threading.local()


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts):
        pass


OFF = _Off()


def enable(flag: bool) -> None:
    """Switch tracing on or off for the whole process."""
    global _on
    _on = bool(flag)
    if not _on:
        release()


def enabled() -> bool:
    return _on


def span(name: str, **ids):
    if not _on:
        return OFF
    if getattr(_local, "held", None) is not None and not getattr(
            _local, "keep", 0):
        release()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, **ids)


def hold(name: str) -> None:
    """Open ``name`` as this thread's held span, unless one is held."""
    if _on and getattr(_local, "held", None) is None:
        from jax.profiler import TraceAnnotation
        _local.held = TraceAnnotation(name)
        _local.held.__enter__()


def release() -> None:
    """End this thread's held span, if any."""
    held = getattr(_local, "held", None)
    if held is not None:
        _local.held = None
        held.__exit__(None, None, None)


class _Keep:
    __slots__ = ()

    def __enter__(self):
        _local.keep = getattr(_local, "keep", 0) + 1

    def __exit__(self, *exc):
        _local.keep -= 1
        return False


def keep_held():
    """Spans opened inside nest under this thread's held span."""
    return _Keep() if _on else OFF
