"""Host spans: named intervals of the program's own work, on the host clock.

    from repro.trace import span, spans
    with span("ckpt.serialize"):
        blob = arr.tobytes()
    spans("ckpt.serialize", t0, t1)   # -> [Span(name, t0, t1, thread, parent)]

A span records ``(name, t0, t1, thread, parent)`` into a bounded in-memory
ring when it ends: ``t0`` and ``t1`` are ``time.perf_counter()`` readings,
``thread`` is the recording thread's ident and ``parent`` the name of the
span enclosing it on the same thread (None at the top).  When the ring is
full the oldest span is overwritten and ``Recorder.dropped`` counts it.
Recording is always on; there is no exporter, file or flag.

Where ``jax`` is already imported, a span also enters
``jax.profiler.TraceAnnotation(name)``, so a profiler trace shows it on
the device trace's clock.  This module never imports ``jax`` itself: the
engine (``repro.core``) stays free of it.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
from typing import NamedTuple, Optional

CAPACITY = 1 << 16


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    thread: int
    parent: Optional[str]


class _Open:
    """One span being timed; ``Recorder.span`` hands it out."""

    __slots__ = ("rec", "name", "parent", "ann", "t0")

    def __init__(self, rec: "Recorder", name: str):
        self.rec, self.name = rec, name

    def __enter__(self) -> "_Open":
        stack = self.rec._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        jax = sys.modules.get("jax")
        self.ann = None
        if jax is not None:
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.rec._stack().pop()
        self.rec._record(Span(self.name, self.t0, t1, threading.get_ident(),
                              self.parent))


class Recorder:
    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self.dropped = 0

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _record(self, s: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(s)

    def span(self, name: str) -> _Open:
        return _Open(self, name)

    def spans(self, name: str | None = None, t0: float | None = None,
              t1: float | None = None) -> list[Span]:
        """The recorded spans, oldest first: those called ``name``, lying
        wholly within ``t0 .. t1`` (either end may be left open)."""
        with self._lock:
            out = list(self._ring)
        return [s for s in out
                if (name is None or s.name == name)
                and (t0 is None or s.t0 >= t0)
                and (t1 is None or s.t1 <= t1)]


RECORDER = Recorder()
span = RECORDER.span
spans = RECORDER.spans
