"""Host milliseconds of the compile-time solve per request: the
benchmark's timer around ``TuningService.tune_batch`` over the requests
it was given."""


def read(run):
    p = run["probes"]
    n = p.counts.get("tune_batch", 0)
    return 1e3 * p.seconds["tune_batch"] / n if n else None
