"""The program's own record of the window.

``OptimizerServer.serve`` keeps one record of spans and counters per call
(``repro.obs.ServeTrace``) on every request it returns.  A span's calls,
total and self seconds are read with ``calls``, ``total_s`` and
``self_s``; a counter with ``counter``.  ``compile_s@<span>`` holds JAX's
backend-compile seconds under that span, persistent-cache loads included
(JAX's compile event wraps the cache read).  A program that keeps no such
record gives ``None``.
"""


def trace(run):
    served = run["served"]
    return getattr(served[0], "trace", None) if served else None


def ms_per(run, seconds, count):
    """``1e3 * seconds(tr) / count(tr)`` of the window's record ``tr``;
    ``None`` without a record or when the count is 0."""
    tr = trace(run)
    if tr is None:
        return None
    n = count(tr)
    return 1e3 * seconds(tr) / n if n else None


def solved(tr):
    """Requests the window actually solved (response-cache hits not)."""
    return tr.counter("solve.solved")
