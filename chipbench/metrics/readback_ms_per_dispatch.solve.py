"""Host milliseconds per ``subq`` model dispatch spent reading its result
back: the program's span ``repro.model.readback.subq`` (the wait for the
device, the transfer, the result slice) less the compile and cache-load
seconds counted under it, over the ``subq`` dispatches of the window (GTN
chunks and regressor chunks)."""
from chipbench.metrics._program import ms_per


def read(run):
    span = "repro.model.readback.subq"
    return ms_per(run, lambda tr: tr.total_s(span)
                  - tr.counter("compile_s@" + span),
                  lambda tr: tr.counter("model.dispatches.subq"))
