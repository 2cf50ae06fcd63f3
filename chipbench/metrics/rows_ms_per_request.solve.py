"""Host milliseconds per solved request spent building the solver's
candidate rows: self time of the program's span ``repro.solve.rows``
(``HmoocPlan.requests`` and ``fused_stage_eval``'s host assembly, the
model's own spans excluded) over the requests the window solved."""
from chipbench.metrics._program import ms_per, solved


def read(run):
    return ms_per(run, lambda tr: tr.self_s("repro.solve.rows"), solved)
