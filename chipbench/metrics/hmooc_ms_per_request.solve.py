"""Host milliseconds per solved request in the HMOOC solver's own work:
self time of the program's spans ``repro.solve.hmooc.banks`` (Algorithm-1
bank builds) and ``repro.solve.hmooc.assign`` (assignment and DAG
aggregation) over the requests the window solved."""
from chipbench.metrics._program import ms_per, solved


def read(run):
    return ms_per(run, lambda tr: tr.self_s("repro.solve.hmooc.banks")
                  + tr.self_s("repro.solve.hmooc.assign"), solved)
