"""``model_groups_per_phase.solve``: the program's ``solve.model_groups``
over ``solve.phases``.

On records built by hand it gives the ratio, and ``None`` without the
counters (as on a program that keeps none), with no phase, or without a
record.  On windows the program served at a tiny size on the CPU it reads
1.0 with one model and above 1.0 when a flush holds two benchmarks'
models.
"""
import importlib.util
import os
import re
import types

import pytest

from repro.obs import ServeTrace

NAME = "model_groups_per_phase.solve"
PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics", NAME + ".py")


def _read(run):
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + NAME.replace(".", "_"), PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _run(counters):
    tr = ServeTrace()
    tr.counters.update(counters)
    return {"served": [types.SimpleNamespace(status="served", trace=tr)]}


@pytest.mark.parametrize("counters,want", [
    ({"solve.phases": 40, "solve.model_groups": 40}, 1.0),
    ({"solve.phases": 40, "solve.model_groups": 58}, 58 / 40),
    ({"solve.solved": 17}, None),
    ({"solve.phases": 0, "solve.model_groups": 0}, None),
], ids=["one_model", "two_models", "without_the_counters", "no_phase"])
def test_value_on_a_hand_built_record(counters, want):
    assert _read(_run(counters)) == want


def test_none_without_a_record():
    s = types.SimpleNamespace(status="served", arrival_s=0.0, admitted_s=0.1)
    assert _read({"served": [s]}) is None
    assert _read({"served": []}) is None


def test_reader_imports_nothing_of_the_program():
    with open(PATH) as f:
        assert not re.search(r"^\s*(from|import)\s+repro", f.read(), re.M)


def _served(models):
    from repro.core.moo.hmooc import HMOOCConfig
    from repro.queryengine.workloads import StreamRequest, make_query
    from repro.serve import OptimizerServer, ServerConfig, TuningService
    cfg = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48,
                      n_c_enrich=12, max_bank=12, seed=3)
    queries = [make_query("tpch", 1), make_query("tpcds", 3),
               make_query("tpch", 5)]
    server = OptimizerServer(config=ServerConfig(max_batch=4),
                             tuning=TuningService(model=models, cfg=cfg))
    return server.serve([StreamRequest(rid=i, query=q, arrival_s=0.0)
                         for i, q in enumerate(queries)])


def test_value_on_windows_the_program_served():
    from repro.core.models.gtn import GTNConfig
    from repro.core.models.perf_model import ModelConfig, PerfModel
    gtn = GTNConfig(d_model=16, n_heads=2, n_layers=1, d_ff=32)
    m = [PerfModel(ModelConfig("subq", 19, gtn=gtn, hidden=(16,)), seed=s)
         for s in (0, 1)]
    assert _read({"served": _served(m[0])}) == 1.0
    assert _read({"served": _served({"tpch": m[0], "tpcds": m[1]})}) > 1.0
