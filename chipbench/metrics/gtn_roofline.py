"""Share of its roofline that the jitted GTN embed reached.

Least time of the graphs the window embedded (larger of operations over
the bf16 peak and bytes over HBM bandwidth) over the summed device time of
``jit__embed_batch`` in the trace, in percent.
"""
from chipbench.metrics import _model_work as W


def read(run):
    return W.roofline_pct(run, W.GTN_PROGRAM, W.gtn)
