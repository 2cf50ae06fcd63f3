"""Host milliseconds of the cluster simulator per finished request: the
benchmark's timer around ``RuntimeSession.realize`` over the plans it
realized."""


def read(run):
    p = run["probes"]
    n = p.counts.get("realize", 0)
    return 1e3 * p.seconds["realize"] / n if n else None
