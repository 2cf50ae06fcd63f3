"""Host milliseconds per runtime re-tuning round spent scoring candidates:
the program's span ``repro.runtime.score`` (``score_requests``, model
dispatches included) over its calls, one per round."""
from chipbench.metrics._program import ms_per


def read(run):
    span = "repro.runtime.score"
    return ms_per(run, lambda tr: tr.total_s(span),
                  lambda tr: tr.calls(span))
