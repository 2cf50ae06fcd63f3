"""Timers, trace spans and counters put around the program's layer calls.

The program records no spans of its own, so the benchmark wraps the calls
into each layer on the instances it serves with (as ``count_routes`` in
``chip_smoke.py`` wraps the routing entry points), and takes the wrappers
off again afterwards:

* ``tune_batch``  -- ``TuningService.tune_batch``: compile-time solve of a
  micro-batch (requests counted);
* ``step_round``  -- ``RuntimeSession.step_round``: one runtime re-tuning round;
* ``realize``     -- ``RuntimeSession.realize``: the cluster simulator runs the
  finished plans (requests counted);
* ``embed_many``  -- ``PerfModel.embed_many``: GTN embeddings (graphs newly
  computed counted);
* ``predict_rows`` -- ``PerfModel.predict_rows``: regressor rows (rows
  counted, padding not).

With ``annotate`` each call is also a ``jax.profiler.TraceAnnotation`` named
``chipbench.<name>``, so the trace's idle gaps can be laid at a layer's door.
A listener counts JAX's traces and compiles, so a run can show that none
happen inside its window.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, Iterable, Tuple

import numpy as np

SPAN_PREFIX = "chipbench."


class Probes:
    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self.calls: Dict[str, int] = collections.Counter()
        self.counts: Dict[str, int] = collections.Counter()
        self._patches = []

    def _wrap(self, owner, attr: str, name: str, count=None, pre=None):
        orig = getattr(owner, attr)
        annotate = self.annotate
        seconds, calls = self.seconds, self.calls
        counts = self.counts

        def timed(*a, **kw):
            if annotate:
                import jax
                ctx = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            else:
                ctx = contextlib.nullcontext()
            state = pre() if pre is not None else None
            t0 = time.perf_counter()
            with ctx:
                out = orig(*a, **kw)
            dt = time.perf_counter() - t0
            seconds[name] += dt
            calls[name] += 1
            if count is not None:
                counts[name] += count(a, out, state)
            return out
        self._patches.append((owner, attr))
        setattr(owner, attr, timed)

    def install(self, server, models: Iterable[Tuple[str, object]]) -> None:
        """Wraps the server's layers and each (kind, model) of ``models``;
        the counts of several models of one kind add up."""
        self._wrap(server.tuning, "tune_batch", "tune_batch",
                   lambda a, out, s: len(a[0]))
        self._wrap(server.session, "step_round", "step_round")
        self._wrap(server.session, "realize", "realize",
                   lambda a, out, s: len(out))
        for kind, m in models:
            self._wrap(m, "embed_many", f"embed_many.{kind}",
                       lambda a, out, s, m=m: len(m._emb_cache) - s,
                       pre=lambda m=m: len(m._emb_cache))
            self._wrap(m, "predict_rows", f"predict_rows.{kind}",
                       lambda a, out, s: int(np.shape(a[1])[0]))

    def remove(self) -> None:
        for owner, attr in reversed(self._patches):
            if attr in vars(owner):
                delattr(owner, attr)
        self._patches.clear()


class HeadRecorder:
    """Keeps a seeded sample of rows of every regressor dispatch.

    Wraps a model's jitted head (``PerfModel._head``) on the instance: for
    each dispatch it copies ``rows`` row indices' inputs and keeps the
    output array, whose rows are read once the window has closed.
    """

    def __init__(self, model, seed: int, rows: int = 16):
        self.model = model
        self.rng = np.random.default_rng(seed)
        self.rows = rows
        self.kept = []
        orig = model._head

        def head(p, e, t, d):
            z = orig(p, e, t, d)
            idx = self.rng.integers(0, e.shape[0], self.rows)
            self.kept.append((e[idx], t[idx], d[idx], idx, z))
            return z
        model._head = head
        self._orig = orig

    def remove(self) -> None:
        self.model._head = self._orig

    def rows_out(self):
        """(emb, theta, nond, z) of the kept rows that are not padding."""
        if not self.kept:
            return None
        e, t, d, z = (np.concatenate(x) for x in zip(
            *[(e, t, d, np.asarray(z)[idx]) for e, t, d, idx, z in self.kept]))
        real = (np.abs(t).sum(1) + np.abs(e).sum(1)) > 0
        return e[real], t[real], d[real], z[real]


@contextlib.contextmanager
def count_compiles():
    """Counts JAX traces, backend compiles and persistent-cache loads."""
    import jax
    from jax._src import dispatch

    counts = collections.Counter()
    live = [True]

    def on_duration(event, duration, **kw):
        if not live[0]:
            return
        if event == dispatch.JAXPR_TRACE_EVENT:
            counts["traces"] += 1
            counts[f"trace:{kw.get('fun_name', '?')}"] += 1
        elif event == dispatch.BACKEND_COMPILE_EVENT:
            counts["compiles"] += 1
            counts["compile_s"] += duration

    def on_event(event, **kw):
        if live[0] and event == "/jax/compilation_cache/cache_hits":
            counts["cache_loads"] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield counts
    finally:
        live[0] = False
