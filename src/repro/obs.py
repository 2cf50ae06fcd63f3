"""Spans and counters inside the program, on the profiler's clock.

One small record per :meth:`OptimizerServer.serve` call says where the
host's time went, layer by layer:

* :class:`span` is a context manager that always opens a
  ``jax.profiler.TraceAnnotation`` of its name (an event on the profiler's
  host plane, on the same clock as the device planes, when a profiler
  session runs; well under a microsecond when none does).  While a
  :class:`ServeTrace` is current it also adds its calls, total seconds and
  self seconds (the duration minus what its child spans cover) to that
  record.  With no record current it keeps no books.
* :func:`count` adds to a named counter of the current record.
* One ``jax.monitoring`` listener, registered when this module is first
  imported, counts JAX's backend compiles under the innermost open span of
  the current record (``compiles@<span>``, ``compile_s@<span>``) and, of
  those, the ones answered from the persistent compilation cache
  (``cache_loads@<span>``, ``cache_load_s@<span>``).  JAX's compile event
  wraps the persistent-cache read, so ``compile_s`` already holds
  ``cache_load_s``.  A compile outside every span counts under ``-``.

The server makes a record current for the length of ``serve()``
(:func:`record`); unit tests and scripts that call a layer directly keep no
books unless they open a record themselves.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import time
from typing import Dict, Iterator, List, Optional

import jax

__all__ = ["ServeTrace", "span", "count", "record"]

_perf_counter = time.perf_counter
_TraceAnnotation = jax.profiler.TraceAnnotation

_CURRENT: contextvars.ContextVar[Optional["ServeTrace"]] = \
    contextvars.ContextVar("repro_obs_trace", default=None)

OUTSIDE = "-"    # the span name a compile outside every span counts under


class ServeTrace:
    """The record of one ``serve()`` call.

    ``spans`` maps a span name to ``[calls, total_s, self_s]``; ``counters``
    maps a counter name to its sum.
    """
    __slots__ = ("spans", "counters", "_open")

    def __init__(self) -> None:
        self.spans: Dict[str, List[float]] = {}
        self.counters: Dict[str, float] = collections.defaultdict(int)
        self._open: List["span"] = []

    def calls(self, name: str) -> int:
        return int(self.spans.get(name, (0, 0.0, 0.0))[0])

    def total_s(self, name: str) -> float:
        return self.spans.get(name, (0, 0.0, 0.0))[1]

    def self_s(self, name: str) -> float:
        return self.spans.get(name, (0, 0.0, 0.0))[2]

    def counter(self, name: str) -> float:
        return self.counters.get(name, 0)


class span:
    """``with span("repro.layer.step"):`` — see the module docstring."""
    __slots__ = ("name", "_ann", "_rec", "_t0", "child_s")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._ann = _TraceAnnotation(self.name)
        self._ann.__enter__()
        rec = self._rec = _CURRENT.get()
        if rec is not None:
            self.child_s = 0.0
            rec._open.append(self)
            self._t0 = _perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        rec = self._rec
        if rec is not None:
            dt = _perf_counter() - self._t0
            stack = rec._open
            stack.pop()
            if stack:
                stack[-1].child_s += dt
            s = rec.spans.get(self.name)
            if s is None:
                s = rec.spans[self.name] = [0, 0.0, 0.0]
            s[0] += 1
            s[1] += dt
            s[2] += dt - self.child_s
        self._ann.__exit__(*exc)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the current record, if any."""
    rec = _CURRENT.get()
    if rec is not None:
        rec.counters[name] += n


@contextlib.contextmanager
def record(trace: Optional[ServeTrace] = None) -> Iterator[ServeTrace]:
    """Make ``trace`` (a new one by default) the current record."""
    rec = trace if trace is not None else ServeTrace()
    token = _CURRENT.set(rec)
    try:
        yield rec
    finally:
        _CURRENT.reset(token)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_duration(event: str, duration: float, **kw) -> None:
    if event != _COMPILE_EVENT and event != _CACHE_LOAD_EVENT:
        return
    rec = _CURRENT.get()
    if rec is None:
        return
    where = rec._open[-1].name if rec._open else OUTSIDE
    if event == _COMPILE_EVENT:
        rec.counters["compiles@" + where] += 1
        rec.counters["compile_s@" + where] += duration
    else:
        rec.counters["cache_loads@" + where] += 1
        rec.counters["cache_load_s@" + where] += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)
