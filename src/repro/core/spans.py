"""Spans: the endpoint's phases on the profiler's clock and in the stamps.

``with span(name):`` marks one phase of work on the calling thread. Where
the process has imported JAX, the span is a ``jax.profiler.TraceAnnotation``:
about a microsecond with no profiler session open, and an event on the
profiler's own timeline, beside the device's ops, when one is. This module
never imports JAX, so ``repro.core`` stays free of it.

A worker binds the task it runs to its thread (:func:`bind`). Spans on that
thread then carry the task id as metadata, the task's first span also
carries ``perf_ns`` (its entry time on :func:`tasks.now`'s clock, in ns),
and a :class:`device_wait` span adds its seconds to the task's
``DEVICE_WAIT`` stamp entry. ``perf_ns`` minus the event's start in the
trace is the offset that places every stamp of the task on the trace's
clock.

Spans are leaves: none opens inside another on one thread, so the host
event covering a device gap names the phase, not an enclosing span.
:func:`stamp` adds a time or a count of the function's own to the bound
task's stamps (the fabric's first token and token count).
"""
from __future__ import annotations

import sys
import threading
from contextlib import contextmanager
from typing import Dict, Iterator, Optional

from .tasks import DEVICE_WAIT, now


class _Bound:
    __slots__ = ("task_id", "stamps", "first")

    def __init__(self, task_id: str, stamps: Dict[str, float]):
        self.task_id = task_id
        self.stamps = stamps
        self.first = True


class _Local(threading.local):
    task: Optional[_Bound] = None      # a class default: no failed lookup


_local = _Local()


@contextmanager
def bind(task_id: str, stamps: Dict[str, float]) -> Iterator[None]:
    """Bind a task's stamps to this thread while its function runs."""
    _local.task = _Bound(task_id, stamps)
    try:
        yield
    finally:
        _local.task = None


def stamp(name: str, value: Optional[float] = None) -> None:
    """Stamp the bound task: ``name`` is ``value``, or now on
    :func:`tasks.now`'s clock. Nothing where no task is bound."""
    task = _local.task
    if task is not None:
        task.stamps[name] = now() if value is None else value


class span:
    """One leaf phase of work on this thread (see the module's doc)."""

    __slots__ = ("name", "_task", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        task = self._task = _local.task
        prof = sys.modules.get("jax.profiler")  # loaded iff JAX is imported
        t0 = self._t0 = now()
        if prof is None:
            ann = None
        elif task is None:
            ann = prof.TraceAnnotation(self.name)
        elif task.first:
            task.first = False
            ann = prof.TraceAnnotation(self.name, task_id=task.task_id,
                                       perf_ns=int(t0 * 1e9))
        else:
            ann = prof.TraceAnnotation(self.name, task_id=task.task_id)
        self._ann = ann
        if ann is not None:
            ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is not None:
            self._ann.__exit__(*exc)


class device_wait(span):
    """A span in which the bound task waits on the device: its seconds add
    to the task's ``DEVICE_WAIT`` stamp entry, the device part of ``t_w``."""

    __slots__ = ()

    def __exit__(self, *exc) -> None:
        task = self._task
        if task is not None:
            stamps = task.stamps
            stamps[DEVICE_WAIT] = (stamps.get(DEVICE_WAIT, 0.0)
                                   + now() - self._t0)
        super().__exit__(*exc)
