"""Spans and counters of the serving path.

One `Recorder`, `DEFAULT`, is shared by the layers of a serving stack:
the front door (`serve.sched.front`), the descriptor engine
(`core.engine`) and the model binding (`serve.sched.steplm`) each read
it at every span.  It is off; setting ``DEFAULT.enabled`` between steps
turns on the spans of every layer at once.

* ``span(name, rid=None)`` — a context manager that records
  ``(name, start_ns, end_ns, parent, rid)`` on `time.perf_counter_ns`;
  ``parent`` is the index in `events` of the enclosing open span,
  ``rid`` the request a span belongs to.  While on, each span is also a
  profiler annotation ``repro.<name>``, so a device trace holds the
  program's spans on its own clock.
* ``begin(name, rid)`` / ``end(name, rid)`` — a request's span across
  steps (queued, staged).  It overlaps the steps' own spans, so it has
  no parent and is kept off the profiler's trace.
* ``count(name, n=1)`` — adds to an integer counter.

Off, ``span()`` returns one shared no-op context and ``count``,
``begin`` and ``end`` return at once: no clock read, no allocation.
On, everything stays in memory until the caller reads `events` and
`counters`.  Turn a recorder on or off between steps, not inside a span.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, int, int, Optional[int], Optional[int]]

ANNOTATION_PREFIX = "repro."


class _Off:
    """The context every span of a recorder that is off returns."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("rec", "name", "rid", "index", "t0", "ann")

    def __init__(self, rec: "Recorder", name: str,
                 rid: Optional[int]) -> None:
        self.rec, self.name, self.rid = rec, name, rid

    def __enter__(self) -> None:
        from jax.profiler import TraceAnnotation
        rec = self.rec
        self.index = len(rec.events)
        rec.events.append((self.name, 0, 0,
                           rec._open[-1] if rec._open else None, self.rid))
        rec._open.append(self.index)
        self.ann = TraceAnnotation(ANNOTATION_PREFIX + self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        rec = self.rec
        rec._open.pop()
        parent = rec.events[self.index][3]
        rec.events[self.index] = (self.name, self.t0, t1, parent, self.rid)
        return False


class Recorder:
    """Host spans and counters; off unless ``enabled``."""

    def __init__(self, enabled: bool = False) -> None:
        self.enabled = enabled
        self.events: List[Event] = []
        self.counters: Dict[str, int] = {}
        self._open: List[int] = []
        self._begun: Dict[Tuple[str, int], int] = {}

    def span(self, name: str, rid: Optional[int] = None):
        if not self.enabled:
            return _OFF
        return _Span(self, name, rid)

    def count(self, name: str, n: int = 1) -> None:
        if self.enabled:
            self.counters[name] = self.counters.get(name, 0) + n

    def begin(self, name: str, rid: int) -> None:
        if self.enabled:
            self._begun[(name, rid)] = time.perf_counter_ns()

    def end(self, name: str, rid: int) -> None:
        """Closes ``begin(name, rid)``; nothing where it was not begun
        while on."""
        if self.enabled:
            t0 = self._begun.pop((name, rid), None)
            if t0 is not None:
                self.events.append((name, t0, time.perf_counter_ns(),
                                    None, rid))

    def clear(self) -> None:
        self.events.clear()
        self.counters.clear()
        self._open.clear()
        self._begun.clear()


#: the recorder of every layer; off
DEFAULT = Recorder()
