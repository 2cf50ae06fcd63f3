"""Host milliseconds per AQE re-tuning request: the total time of the
program's spans ``repro.runtime.candidates``, ``.score``, ``.pick`` and
``.aqe`` (the parts of ``RuntimeSession.step_round``) over its counter
``runtime.requests``, the requests the rounds serviced (one per LQP or QS
event)."""
from chipbench.metrics._program import ms_per

SPANS = tuple(f"repro.runtime.{p}" for p in ("candidates", "score", "pick",
                                             "aqe"))


def read(run):
    return ms_per(run, lambda tr: sum(tr.total_s(s) for s in SPANS),
                  lambda tr: tr.counter("runtime.requests"))
