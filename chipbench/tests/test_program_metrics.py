"""The per-layer metrics that read the program's own spans and counters.

Each reader gets ``run["served"]`` of a window served by the program at a
tiny size on the CPU: it must give a finite number there, and ``None`` on
a window in which nothing was solved and on a program that keeps no
record.
"""
import importlib.util
import math
import os
import re
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
READERS = ["admission_busy_wait_ms", "hmooc_ms_per_request.solve",
           "rows_ms_per_request.solve", "featurize_ms_per_request.solve",
           "readback_ms_per_dispatch.solve", "compile_ms_per_request",
           "score_ms_per_round"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _server(tenants=()):
    from repro.core.models.gtn import GTNConfig
    from repro.core.models.perf_model import ModelConfig, PerfModel
    from repro.core.moo.hmooc import HMOOCConfig
    from repro.serve import (OptimizerServer, RuntimeSession, ServerConfig,
                             TuningService)
    gtn = GTNConfig(d_model=16, n_heads=2, n_layers=1, d_ff=32)
    msub = PerfModel(ModelConfig("subq", 19, gtn=gtn, hidden=(16,)), seed=0)
    mqs = PerfModel(ModelConfig("qs", 10, gtn=gtn, hidden=(16,)), seed=1)
    cfg = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48,
                      n_c_enrich=12, max_bank=12, seed=3)
    return OptimizerServer(
        config=ServerConfig(max_batch=2), tenants=tenants,
        tuning=TuningService(model=msub, cfg=cfg),
        session=RuntimeSession(model_subq=msub, model_qs=mqs,
                               weights=(0.9, 0.1)))


def _stream(tenant="default"):
    from repro.queryengine.workloads import StreamRequest, make_benchmark
    queries = make_benchmark("tpch")[:4]
    return [StreamRequest(rid=i, query=q, arrival_s=0.05 * i, tenant=tenant)
            for i, q in enumerate(queries)]


@pytest.fixture(scope="module")
def windows():
    from repro.queryengine.workloads import TenantSpec
    served = _server().serve(_stream())
    # A strict tenant whose budget no solve can meet: every request is shed.
    tenant = TenantSpec(name="strict", slo="strict", solve_budget_s=1e-6)
    shed = _server([tenant]).serve(_stream("strict"))
    assert {s.status for s in served} == {"served"}
    assert {s.status for s in shed} == {"shed"}
    return {"served": served}, {"served": shed}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_served_window(name, windows):
    value = _reader(name)(windows[0])
    assert value is not None and math.isfinite(value) and value >= 0, value


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_window_that_never_solved(name, windows):
    assert _reader(name)(windows[1]) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_program_that_keeps_no_record(name):
    """A program without the record (its requests carry no ``trace`` or
    ``busy_wait_s``) gives no value, and no error."""
    s = types.SimpleNamespace(status="served", arrival_s=0.0, admitted_s=0.1)
    assert _reader(name)({"served": [s]}) is None


def test_readers_import_nothing_of_the_program():
    for name in READERS + ["_program"]:
        with open(os.path.join(METRICS, name + ".py")) as f:
            assert not re.search(r"^\s*(from|import)\s+repro", f.read(),
                                 re.M), name
