"""Share of its roofline that the jitted regressor head reached.

Least time of the head rows the window computed (larger of operations
over the bf16 peak and bytes over HBM bandwidth) over the summed device
time of ``jit__head_fn`` in the trace, in percent.
"""
from chipbench.metrics import _model_work as W


def read(run):
    return W.roofline_pct(run, W.HEAD_PROGRAM, W.head)
