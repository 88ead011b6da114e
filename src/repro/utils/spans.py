"""Program spans on the profiler's clock.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` whose ``ids`` become the event's stats. While a
profiler session runs (``jax.profiler.start_trace``) it lands in the
trace's host plane, on the same clock as the device's operations, so an
idle gap of the device can be put down to what the host was doing.
With no session running it costs about a microsecond. There is no
switch: tracing is on exactly while a profiler session runs.

``scope(**ids)`` gives every span opened inside it, on the same thread,
those ids too: a round sets its tenant and sequence number once, and
the store's and the engine's spans beneath it carry them.

A span never straddles a ``yield`` (it would close on another frame's
time) and never sits inside a jitted body (it would time the trace).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Iterator

import jax

PREFIX = "repro."

_local = threading.local()


def span(name: str, **ids):
    """A host span ``repro.<name>`` with ``ids`` (and the thread's
    ``scope`` ids) as its stats."""
    scoped = getattr(_local, "ids", None)
    if scoped:
        ids = {**scoped, **ids}
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


@contextlib.contextmanager
def scope(**ids) -> Iterator[None]:
    """Ids that every span this thread opens inside carries."""
    prev = getattr(_local, "ids", None)
    _local.ids = {**(prev or {}), **ids}
    try:
        yield
    finally:
        _local.ids = prev


class DeviceSlot:
    """A device semaphore (or ``None``: no bound) held as a context
    manager, with the wait for it in a ``repro.engine.device_wait`` span
    of its own, apart from the work done while holding it."""

    def __init__(self, sem=None):
        self._sem = sem

    def __enter__(self) -> "DeviceSlot":
        if self._sem is not None:
            with span("engine.device_wait"):
                self._sem.acquire()
        return self

    def __exit__(self, *exc) -> None:
        if self._sem is not None:
            self._sem.release()
