"""Host milliseconds per solved request spent featurising subQ graphs for
the GTN: the program's span ``repro.model.featurize.subq``
(``featurize_subq`` and ``batch_graphs``) over the requests the window
solved."""
from chipbench.metrics._program import ms_per, solved


def read(run):
    return ms_per(run, lambda tr: tr.total_s("repro.model.featurize.subq"),
                  solved)
