"""Spans of the workflow executor, on the profiler's clock.

One process-wide tracer, off by default. It records while it is enabled
(:func:`enable` ... :func:`disable`) and while a JAX profiler session
collects host events (``jax.profiler.trace`` / ``start_trace``), so a
profile of a running executor carries the program's spans beside the
device's operations, and an operator who profiles gets them without a
second switch.

* :func:`span` is a context manager. Off, it returns one shared no-op object
  (falsy, so ``if sp:`` guards attributes that cost something to compute):
  no span object, no clock read, no annotation. On, a :class:`Span` records
  its name, an id, the id of the span that caused it, the thread's ident and
  ``perf_counter_ns`` at start and end, and enters
  ``jax.profiler.TraceAnnotation(name)``.
* ``enable(cpu_time=True)`` also records ``thread_time_ns`` at both ends:
  wall minus thread CPU time is the time the thread spent off the CPU,
  waiting for the interpreter lock, a lock, I/O or the scheduler. It is off
  by default and while the tracer only follows a profiler session, because
  the thread CPU clock is a system call (several microseconds a span on
  some hosts) and may move in steps as coarse as the scheduler's tick (10 ms
  on some), so it resolves a long stall but not one short span.
* The causing span is the innermost open span of the same thread. Work
  handed to another thread names it explicitly: ``span(..., cause=id)``
  with ``id = current()`` read by the handing thread, or :func:`handoff`.
* :func:`drain` returns the finished spans and clears them; :func:`peek`
  returns them and keeps them. Records stay in memory until drained; past
  ``MAX_SPANS`` kept spans, further spans are not kept, and
  ``TRACER.dropped`` counts them until the next drain.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

MAX_SPANS = 1 << 20


class _Off:
    """The span returned while the tracer is off."""
    __slots__ = ()
    id = None

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def __bool__(self) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


OFF = _Off()


class Span:
    """One recorded span; times in nanoseconds."""
    __slots__ = ("name", "id", "parent", "thread", "attrs", "start_ns",
                 "end_ns", "cpu_start_ns", "cpu_end_ns", "_tracer", "_note")

    def __init__(self, tracer: "Tracer", name: str, cause: Optional[int],
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.id = next(tracer._ids)
        self.parent = cause
        self.thread = threading.get_ident()
        self.attrs = attrs
        self.start_ns = self.end_ns = 0
        self.cpu_start_ns = self.cpu_end_ns = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def wall_ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def cpu_ns(self) -> Optional[int]:
        """Thread CPU time, or None where the tracer did not read it."""
        if self.cpu_start_ns is None:
            return None
        return self.cpu_end_ns - self.cpu_start_ns

    def __enter__(self) -> "Span":
        stack = self._tracer._stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.id)
        self._note = _annotation()(self.name)
        self._note.__enter__()
        if self._tracer.cpu_time:
            self.cpu_start_ns = time.thread_time_ns()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self.cpu_start_ns is not None:
            self.cpu_end_ns = time.thread_time_ns()
        self._note.__exit__(*exc)
        self._note = None
        self._tracer._stack().pop()
        self._tracer._keep(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"thread={self.thread}, wall_ns={self.wall_ns}, "
                f"cpu_ns={self.cpu_ns}, attrs={self.attrs})")


class Tracer:
    def __init__(self, max_spans: int = MAX_SPANS):
        self.enabled = False
        self.cpu_time = False
        self.max_spans = max_spans
        self.dropped = 0
        self._spans: List[Span] = []
        self._ids = itertools.count(1)
        self._mu = threading.Lock()
        self._local = threading.local()

    def span(self, name: str, cause: Optional[int] = None, **attrs):
        if not (self.enabled or _profiling()):
            return OFF
        return Span(self, name, cause, attrs)

    def current(self) -> Optional[int]:
        """Id of this thread's innermost open span (None: none, or off)."""
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def handoff(self, fn: Callable, name: str) -> Callable:
        """``fn`` to be run on another thread inside a span ``name`` caused
        by the span open here; ``fn`` itself while the tracer is off."""
        if not (self.enabled or _profiling()):
            return fn
        cause = self.current()

        def run(*a, **kw):
            with self.span(name, cause=cause):
                return fn(*a, **kw)
        return run

    def drain(self) -> List[Span]:
        with self._mu:
            out, self._spans, self.dropped = self._spans, [], 0
        return out

    def peek(self) -> List[Span]:
        with self._mu:
            return list(self._spans)

    def _stack(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _keep(self, sp: Span) -> None:
        with self._mu:
            if len(self._spans) < self.max_spans:
                self._spans.append(sp)
            else:
                self.dropped += 1


_IS_PROFILING: Optional[Callable[[], bool]] = None
_ANNOTATION = None


def _profiling() -> bool:
    """Whether a JAX profiler session is collecting host events. A process
    that has not imported JAX has none, and is not made to import it."""
    global _IS_PROFILING
    if _IS_PROFILING is None:
        if "jax" not in sys.modules:
            return False
        from jax.profiler import TraceAnnotation
        _IS_PROFILING = TraceAnnotation.is_enabled
    return _IS_PROFILING()


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


TRACER = Tracer()
span = TRACER.span
current = TRACER.current
handoff = TRACER.handoff
drain = TRACER.drain
peek = TRACER.peek


def enable(cpu_time: bool = False) -> None:
    """Record spans; with ``cpu_time``, their thread CPU time too."""
    TRACER.cpu_time = cpu_time
    TRACER.enabled = True


def disable() -> None:
    TRACER.enabled = False
    TRACER.cpu_time = False
