"""Lightweight span tracing with an explicit device-sync boundary.

``with tracer.span("pump.chunk", job=jid) as sp: ...`` times a named
region on an injectable monotonic clock and appends the finished span to
a bounded in-memory ring (oldest evicted first).  Spans nest per thread
— the parent id is whatever span is open on the current thread — so a
wave's ``serve_wave.drain`` span owns its per-job children without any
global context plumbing.

Every span is also a ``jax.profiler.TraceAnnotation``: under a profiler
it lands on the host timeline, the clock the device's operations are on,
so an idle gap of the device can be put down to the span open at the
time.  The program's hot path (engine chunks, readouts, ``init_state``)
uses the module-level :func:`span`: a bare annotation (a no-op without a
profiler) unless :func:`install` gave it a :class:`Tracer` whose ring
should record those spans too.  Hot-path spans carry no attributes and
no sync, so they add no host<->device synchronisation and no device op.

JAX dispatch is asynchronous: a chunk launch returns before the device
finishes, so a naive ``perf_counter`` pair around ``chunk_fn`` would
attribute device time to whichever *later* span happens to block.  A
span therefore carries an explicit sync boundary: ``sp.sync(value)``
stashes a pytree (e.g. the returned state) and the tracer calls
``jax.block_until_ready`` on it *before* taking the end timestamp, so
device work lands in the span that launched it.  The blocker is lazy
and injectable — only a span that syncs needs more of jax than its
profiler annotations.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Tracer", "span", "install"]


def _default_block(value: Any) -> None:
    import jax
    jax.block_until_ready(value)


class Span:
    """One timed region; exposed to the ``with`` body for attrs/sync."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "thread",
                 "t0", "t1", "_sync")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 thread: str, t0: float, attrs: dict):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.thread = thread
        self.t0 = t0
        self.t1: Optional[float] = None
        self.attrs = attrs
        self._sync: Any = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def sync(self, value: Any) -> Any:
        """Register a pytree to block on before the end timestamp."""
        self._sync = value
        return value

    @property
    def duration_s(self) -> Optional[float]:
        return None if self.t1 is None else self.t1 - self.t0

    def to_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "thread": self.thread,
                "t0": self.t0, "t1": self.t1,
                "duration_s": self.duration_s, "attrs": dict(self.attrs)}


class Tracer:
    """Bounded span recorder with per-thread nesting.

    ``clock`` must be monotonic (default ``time.perf_counter``);
    ``block`` is called with a span's sync payload before the end stamp
    (default: lazy ``jax.block_until_ready``).
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 capacity: int = 4096,
                 block: Callable[[Any], None] = _default_block):
        self._clock = clock
        self._block = block
        self._ring: deque = deque(maxlen=int(capacity))
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._tls = threading.local()

    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, sync: Any = None, **attrs):
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        with self._lock:
            sid = next(self._ids)
        sp = Span(name, sid, parent, threading.current_thread().name,
                  self._clock(), attrs)
        if sync is not None:
            sp._sync = sync
        stack.append(sp)
        with TraceAnnotation(name):
            try:
                yield sp
            finally:
                if sp._sync is not None:
                    self._block(sp._sync)
                sp.t1 = self._clock()
                if stack and stack[-1] is sp:
                    stack.pop()
                with self._lock:
                    self._ring.append(sp)

    # -- readers --------------------------------------------------------------------

    def spans(self, name: Optional[str] = None) -> List[dict]:
        with self._lock:
            out = [s.to_dict() for s in self._ring]
        if name is not None:
            out = [s for s in out if s["name"] == name]
        return out

    def durations(self, name: str) -> List[float]:
        return [s["duration_s"] for s in self.spans(name)
                if s["duration_s"] is not None]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


_installed: Optional[Tracer] = None


def install(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Record the program's hot-path spans (:func:`span`) in ``tracer``'s
    ring as well as on the profiler's timeline; ``None`` stops recording.
    Returns the tracer it replaces."""
    global _installed
    prev, _installed = _installed, tracer
    return prev


def span(name: str):
    """A hot-path span: a profiler annotation, and a ring span of the
    installed :class:`Tracer` when there is one."""
    tracer = _installed
    if tracer is None:
        return TraceAnnotation(name)
    return tracer.span(name)
