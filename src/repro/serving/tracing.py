"""Program-side spans for the serving path (DESIGN.md §Program spans).

One :class:`Tracer` per fleet (``FleetRuntime.tracer``): the gateway,
the runtime's routing and C&R, and every engine record into it. It is
off by default; ``start()`` and ``stop()`` are its one switch.

* Off, a span site costs one flag test and gets a shared null context:
  no annotation is built and no clock is read.
* On, a span opens a ``jax.profiler.TraceAnnotation`` of its name, so
  a profiler trace shows it on the host plane, on the device trace's
  clock, and keeps a :class:`Span` (times from
  ``time.perf_counter_ns``) in a bounded in-memory list. ``take()``
  hands the list over; nothing is written while spans are recorded.
  Python GC pauses (``host.gc``) and backend compiles
  (``host.compile``) are recorded too, while the tracer is on.

Parents follow the code's nesting through a context variable, so each
asyncio task and each ``handoff`` to an executor thread sees its own
chain. Spans measured after the fact (``interval``: the hop onto the
executor, a compile, C&R timed by the router) exist only in memory: an
annotation cannot be back-dated. To line an in-memory span up with its
profiler twin, read ``time.perf_counter_ns()`` next to an annotation
opened at trace start: the annotation's start in the trace minus that
reading is the offset between the two clocks.
"""
from __future__ import annotations

import contextvars
import gc
import itertools
import time
from contextlib import nullcontext
from typing import Callable, List, NamedTuple

from jax import monitoring
from jax.profiler import TraceAnnotation

_NULL = nullcontext()
# id of the innermost open span in this task / thread (0: none)
_CURRENT: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_serving_span", default=0)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# spans kept at most (~150 MB); later ones are counted, not kept
CAPACITY = 1 << 20


class Span(NamedTuple):
    name: str
    t0: int          # time.perf_counter_ns()
    t1: int
    sid: int
    parent: int      # 0: no parent
    pool: str = ""
    rid: int = -1
    kind: str = ""   # eng.step: idle / prefill / mixed / decode


class _Open:
    """One span while it is open (only built while the tracer is on)."""
    __slots__ = ("tracer", "name", "pool", "rid", "kind", "sid", "parent",
                 "token", "ann", "t0")

    def __init__(self, tracer: "Tracer", name: str, pool: str, rid: int):
        self.tracer, self.name, self.pool, self.rid = tracer, name, pool, rid
        self.kind = ""

    def __enter__(self) -> "_Open":
        self.sid = next(self.tracer._ids)
        self.parent = _CURRENT.get()
        self.token = _CURRENT.set(self.sid)
        self.ann = TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        self.ann.__exit__(None, None, None)
        _CURRENT.reset(self.token)
        self.tracer._keep(Span(self.name, self.t0, t1, self.sid,
                               self.parent, self.pool, self.rid, self.kind))
        return False


class Tracer:
    """Spans of one fleet; see the module docstring."""

    def __init__(self):
        self.on = False
        self.spans: List[Span] = []
        self.dropped = 0              # spans refused once the list was full
        self._ids = itertools.count(1)
        self._gc_open = None

    # ----------------------------------------------------------- switch
    def start(self) -> None:
        if self.on:
            return
        self.on = True
        gc.callbacks.append(self._on_gc)
        monitoring.register_event_duration_secs_listener(self._on_compile)

    def stop(self) -> None:
        if not self.on:
            return
        self.on = False
        gc.callbacks.remove(self._on_gc)
        monitoring.unregister_event_duration_listener(self._on_compile)
        self._gc_open = None

    def take(self) -> List[Span]:
        """The spans recorded so far, handed over (the list restarts)."""
        out, self.spans = self.spans, []
        return out

    # ------------------------------------------------------------ spans
    def span(self, name: str, pool: str = "", rid: int = -1):
        """Context manager over one span; ``as`` gives the open span
        (None while off), whose ``kind`` may be set before it closes."""
        if not self.on:
            return _NULL
        return _Open(self, name, pool, rid)

    def interval(self, name: str, t0: int, t1: int, rid: int = -1,
                 parent: int = -1) -> None:
        """A span timed elsewhere, kept in memory only; its parent is
        the innermost open span unless given."""
        if not self.on:
            return
        if parent < 0:
            parent = _CURRENT.get()
        self._keep(Span(name, t0, t1, next(self._ids), parent, rid=rid))

    def handoff(self, name: str, fn: Callable) -> Callable:
        """``fn`` made ready for an executor thread: on, it records the
        hop (``name``, from now until the thread starts ``fn``) and
        runs ``fn`` in the caller's context, so the spans it opens have
        the caller's span as parent."""
        if not self.on:
            return fn
        ctx = contextvars.copy_context()
        t0 = time.perf_counter_ns()

        def run():
            self.interval(name, t0, time.perf_counter_ns(),
                          parent=ctx.get(_CURRENT, 0))
            return ctx.run(fn)
        return run

    def _keep(self, span: Span) -> None:
        if len(self.spans) < CAPACITY:
            self.spans.append(span)
        else:
            self.dropped += 1

    # ---------------------------------------------- host pauses, compiles
    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = TraceAnnotation("host.gc")
            ann.__enter__()
            self._gc_open = (ann, time.perf_counter_ns())
        elif self._gc_open is not None:
            t1 = time.perf_counter_ns()
            ann, t0 = self._gc_open
            self._gc_open = None
            ann.__exit__(None, None, None)
            self._keep(Span("host.gc", t0, t1, next(self._ids), 0,
                            kind=f"gen{info.get('generation', '?')}"))

    def _on_compile(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            t1 = time.perf_counter_ns()
            self._keep(Span("host.compile", t1 - int(duration * 1e9), t1,
                            next(self._ids), 0,
                            kind=str(kw.get("fun_name", ""))))
