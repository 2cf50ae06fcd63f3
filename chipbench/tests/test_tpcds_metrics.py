"""The per-subQ and per-event readers, on records built by hand.

``hmooc_ms_per_subq.solve`` divides the self time of the two HMOOC spans
by ``solve.subqs``; ``retune_ms_per_event`` divides the total time of the
four ``step_round`` spans by ``runtime.requests``.  Without the counter,
as on a program that keeps none, or with a count of 0, they give ``None``.
"""
import importlib.util
import os
import re
import types

import pytest

from repro.obs import ServeTrace

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(spans, counters):
    tr = ServeTrace()
    tr.spans.update({k: list(v) for k, v in spans.items()})
    tr.counters.update(counters)
    return {"served": [types.SimpleNamespace(status="served", trace=tr)]}


# [calls, total_s, self_s] per span; a parent span and a span of another
# layer that the readers must leave out.
SPANS = {
    "repro.serve.flush": [3, 9.0, 0.5],
    "repro.solve.hmooc.banks": [4, 0.9, 0.6],
    "repro.solve.hmooc.assign": [5, 1.2, 0.4],
    "repro.solve.rows": [9, 3.0, 2.0],
    "repro.serve.round": [7, 5.0, 0.25],
    "repro.runtime.candidates": [7, 0.5, 0.5],
    "repro.runtime.score": [7, 1.5, 0.75],
    "repro.runtime.pick": [7, 0.25, 0.25],
    "repro.runtime.aqe": [7, 0.75, 0.75],
    "repro.runtime.realize": [2, 4.0, 4.0],
}
CASES = {
    "hmooc_ms_per_subq.solve": ("solve.subqs", 250, 1e3 * (0.6 + 0.4) / 250),
    "retune_ms_per_event": ("runtime.requests", 40,
                            1e3 * (0.5 + 1.5 + 0.25 + 0.75) / 40),
}


@pytest.mark.parametrize("name", CASES)
def test_value_on_a_hand_built_record(name):
    counter, n, want = CASES[name]
    got = _reader(name)(_run(SPANS, {counter: n, "solve.solved": 17}))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("count", [None, 0],
                         ids=["without_the_counter", "zero_count"])
def test_none_without_a_count(name, count):
    counters = {"solve.solved": 17}
    if count is not None:
        counters[CASES[name][0]] = count
    assert _reader(name)(_run(SPANS, counters)) is None


@pytest.mark.parametrize("name", CASES)
def test_none_without_a_record(name):
    s = types.SimpleNamespace(status="served", arrival_s=0.0, admitted_s=0.1)
    assert _reader(name)({"served": [s]}) is None
    assert _reader(name)({"served": []}) is None


def test_readers_import_nothing_of_the_program():
    for name in CASES:
        with open(os.path.join(METRICS, name + ".py")) as f:
            assert not re.search(r"^\s*(from|import)\s+repro", f.read(),
                                 re.M), name
