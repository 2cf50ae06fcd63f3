"""Host milliseconds per runtime re-tuning round: the benchmark's timer
around ``RuntimeSession.step_round`` over its calls."""


def read(run):
    p = run["probes"]
    n = p.calls.get("step_round", 0)
    return 1e3 * p.seconds["step_round"] / n if n else None
