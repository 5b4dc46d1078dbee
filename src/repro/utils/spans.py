"""Named host spans on the profiler's clock.

``span(name, **ids)`` brackets one phase of the runtime. It enters
``jax.profiler.TraceAnnotation(name, **ids)``, so under a profiler the span
lands on the host plane, on the same clock as the device planes; it times
itself with ``perf_counter`` (``.seconds``), so a record field filled from
it and the span in the trace are one measurement; and it appends
``(name, t0, t1, ids, thread)`` to a bounded in-memory ring that
:func:`recent` returns.

Names are ``liver/<phase>``. Ids are ``step=<n>`` on the spans of one train
step and ``gen=<id>`` on the spans of one reconfiguration, so the spans of
one step or one resize share an identifier. With the profiler off a span
costs a few microseconds.
"""

from __future__ import annotations

import collections
import threading
import time

import jax

RING_SIZE = 4096
_RING: collections.deque = collections.deque(maxlen=RING_SIZE)


class span:
    """Context manager: one named, timed host span (see the module doc)."""

    __slots__ = ("name", "ids", "t0", "t1", "_annotation")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids
        self.t0 = self.t1 = None

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        _RING.append((self.name, self.t0, self.t1, self.ids, threading.get_ident()))
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def recent() -> list[tuple]:
    """The newest closed spans, oldest first: ``(name, t0, t1, ids, thread)``
    with ``perf_counter`` times, at most :data:`RING_SIZE` of them."""
    return list(_RING)
