"""Model operations of the traced window over (window x bf16 peak), in %.

The model runs float32 at ``Precision.HIGHEST``, which has no published
peak of its own; the bf16 peak is the denominator.
"""
from chipbench.metrics import _model_work as W


def read(run):
    tr = run.get("trace")
    if tr is None or tr["window_s"] <= 0:
        return None
    f = W.gtn(run)[0] + W.head(run)[0]
    if f <= 0:
        return None
    return 100.0 * f / (tr["window_s"] * run["peak"]["bf16_flops_per_s"])
