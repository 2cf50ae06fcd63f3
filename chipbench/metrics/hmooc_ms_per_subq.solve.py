"""Host milliseconds per solved subQ in the HMOOC solver's own work: self
time of the program's spans ``repro.solve.hmooc.banks`` (Algorithm-1 bank
builds) and ``repro.solve.hmooc.assign`` (assignment and DAG aggregation)
over the program's counter ``solve.subqs``, the subQs of the requests the
window solved.  The solver's cost with query size taken out, so cells with
small and large DAGs read alike."""
from chipbench.metrics._program import ms_per


def read(run):
    return ms_per(run, lambda tr: tr.self_s("repro.solve.hmooc.banks")
                  + tr.self_s("repro.solve.hmooc.assign"),
                  lambda tr: tr.counter("solve.subqs"))
