"""Model dispatch groups per lockstep solve phase: the program's counter
``solve.model_groups`` (its ``fused_stage_eval`` calls, one per model with
rows in a phase) over ``solve.phases`` (the phases its micro-batched
solves ran).  1.0 where every phase's rows come from one model; it grows
as the phases of a flush split across benchmarks' models.  ``None`` on a
program without the counters or a window that ran no phase."""
from chipbench.metrics._program import trace


def read(run):
    tr = trace(run)
    if tr is None:
        return None
    phases = tr.counter("solve.phases")
    return tr.counter("solve.model_groups") / phases if phases else None
