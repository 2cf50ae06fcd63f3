"""Milliseconds of JAX compiles (persistent-cache loads included) inside
the window per served request: the program's ``compile_s@<span>``
counters, summed over every span, over the window's served requests."""
from chipbench.metrics._program import ms_per


def read(run):
    return ms_per(run, lambda tr: sum(
        v for k, v in tr.counters.items() if k.startswith("compile_s@")),
        lambda tr: sum(s.status == "served" for s in run["served"]))
