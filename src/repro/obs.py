"""Spans and counters of the sketch serving path.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: inside a
profiler session (``jax.profiler.trace``) it records a host event on the
same clock and in the same ``.xplane.pb`` as the device operations, its
``args`` as the event's stats; outside one it does nothing but enter and
leave.  Spans nest by the ``with`` blocks of one host thread.  They are
opened only in host code, never inside a jitted function.

``count(name, n)`` adds to a process-wide table of host integers and
``counters()`` returns a copy of it.  Call sites add once per call, from
host shapes, never per row and never from a device value, so reading a
counter waits on nothing.  A reader takes the difference of two copies.

The span and counter names are listed in docs/architecture.md
("Observability").
"""
from __future__ import annotations

import threading
from typing import Dict

import jax

_lock = threading.Lock()
_counts: Dict[str, int] = {}


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with small integer ``args``."""
    return jax.profiler.TraceAnnotation(name, **args)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + int(n)


def counters() -> Dict[str, int]:
    """A copy of every counter."""
    with _lock:
        return dict(_counts)
