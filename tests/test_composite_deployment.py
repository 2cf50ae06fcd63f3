"""One server for several benchmarks, each query on its own model pair.

``TuningService`` and ``RuntimeSession`` take either one ``PerfModel`` for
every benchmark or a ``{benchmark: PerfModel}`` mapping.  On the CPU, with
seeded random models that differ per benchmark (so a query routed to the
wrong pair would give other numbers):

* one ``OptimizerServer`` with mappings serves an interleaved TPC-H and
  TPC-DS stream, with both benchmarks in one flush, and every result is
  bit for bit the sequential ``compile_time_optimize`` + ``run_with_aqe``
  path with that benchmark's own models;
* a mixed ``tune_batch`` equals the two single-benchmark batches, with two
  model groups per lockstep phase;
* the response cache and the degraded path key and solve with the query's
  own model, so replacing one benchmark's model leaves the other's entries;
* a benchmark the mapping lacks raises, naming it and the mapping's keys;
* one model keeps the one-benchmark path: one model group per phase, and
  the same results and counters as a mapping of that one model;
* the per-benchmark counters add up to the totals.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check  # noqa: E402
from repro import obs  # noqa: E402
from repro.core.models.gtn import GTNConfig  # noqa: E402
from repro.core.models.perf_model import ModelConfig, PerfModel  # noqa: E402
from repro.core.moo.hmooc import HMOOCConfig  # noqa: E402
from repro.core.tuning.compile_time import (  # noqa: E402
    compile_time_optimize, default_theta_result)
from repro.queryengine.workloads import StreamRequest, make_query  # noqa: E402
from repro.serve import (OptimizerServer, RuntimeSession,  # noqa: E402
                         ServerConfig, ServiceTimeModel, TuningService)
from repro.serve.cache import model_fingerprint  # noqa: E402
from repro.serve.service import model_of  # noqa: E402

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)
WEIGHTS = (0.9, 0.1)
GTN = GTNConfig(d_model=16, n_heads=2, n_layers=1, d_ff=32)
CLOCK = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)), round_s=0.01)
CT_FIELDS = ("front", "choice", "theta_c", "theta_p_sub", "theta_s_sub",
             "theta_p0", "theta_s0")
# (benchmark, template, arrival): both benchmarks at 0.0, so the first
# flush holds both; the rest arrive while the first flush and rounds run.
STREAM = [("tpch", 1, 0.0), ("tpcds", 3, 0.0), ("tpch", 5, 0.0),
          ("tpcds", 18, 0.06), ("tpch", 9, 0.07), ("tpcds", 2, 0.3),
          ("tpch", 15, 0.31)]


def _model(kind, seed):
    theta = {"subq": 19, "qs": 10}[kind]
    return PerfModel(ModelConfig(kind, theta, gtn=GTN, hidden=(16,)),
                     seed=seed)


def _pair(seed):
    return {"subq": _model("subq", seed), "qs": _model("qs", seed + 1)}


@pytest.fixture(scope="module")
def pairs():
    return {"tpch": _pair(10), "tpcds": _pair(20)}


def _maps(pairs, kind):
    return {b: p[kind] for b, p in pairs.items()}


def _queries(spec, variant=0):
    return [make_query(b, t, variant=variant) for b, t, *_ in spec]


def _same_ct(a, b):
    return all(np.array_equal(getattr(a, f), getattr(b, f))
               for f in CT_FIELDS)


# -- the routing rule ---------------------------------------------------------

def test_model_of_routes_a_mapping_by_benchmark(pairs):
    h, ds = _queries([("tpch", 1), ("tpcds", 3)])
    subq = _maps(pairs, "subq")
    assert model_of(subq, h) is pairs["tpch"]["subq"]
    assert model_of(subq, ds) is pairs["tpcds"]["subq"]
    one = pairs["tpch"]["subq"]
    assert model_of(one, h) is one and model_of(one, ds) is one
    assert model_of(None, ds) is None


def test_a_benchmark_the_mapping_lacks_raises(pairs):
    (ds,) = _queries([("tpcds", 3)])
    only = {"tpch": pairs["tpch"]["subq"]}
    with pytest.raises(KeyError, match=r"'tpcds'.*\['tpch'\]"):
        model_of(only, ds)
    with pytest.raises(KeyError, match=r"'tpcds'.*\['tpch'\]"):
        TuningService(model=only, cfg=CFG).tune_batch([ds])
    session = RuntimeSession(model_subq=only,
                             model_qs={"tpch": pairs["tpch"]["qs"]})
    ct = compile_time_optimize(ds, weights=WEIGHTS, cfg=CFG)
    with pytest.raises(KeyError, match=r"'tpcds'.*\['tpch'\]"):
        session.admit(ds, ct)


# -- one server, two benchmarks ----------------------------------------------

@pytest.fixture(scope="module")
def window(pairs):
    server = OptimizerServer(
        config=ServerConfig(clock=CLOCK),
        tuning=TuningService(model=_maps(pairs, "subq"), cfg=CFG),
        session=RuntimeSession(model_subq=_maps(pairs, "subq"),
                               model_qs=_maps(pairs, "qs"), weights=WEIGHTS))
    reqs = [StreamRequest(rid=i, query=q, arrival_s=spec[2])
            for i, (q, spec) in enumerate(zip(_queries(STREAM), STREAM))]
    return server.serve(reqs)


def test_a_flush_holds_both_benchmarks(window):
    assert [s.status for s in window] == ["served"] * len(STREAM)
    flushes = {}
    for s in window:
        flushes.setdefault(s.flush_id, set()).add(s.request.query.benchmark)
    assert {"tpch", "tpcds"} in flushes.values()
    assert any(s.joined_running for s in window)


@pytest.mark.parametrize("bench", ["tpch", "tpcds"])
def test_every_result_is_the_sequential_path_with_its_own_models(
        window, pairs, bench):
    mine = [s for s in window if s.request.query.benchmark == bench]
    n_bad, bad = check.sequential_diffs(mine, pairs[bench], CFG, WEIGHTS)
    assert n_bad == 0, bad
    # And not the other benchmark's: the pairs give other answers.
    other = pairs["tpcds" if bench == "tpch" else "tpch"]
    assert check.sequential_diffs(mine, other, CFG, WEIGHTS)[0] > 0


def test_per_benchmark_counters_add_up_to_the_totals(window):
    tr = window[0].trace
    n = {b: sum(s.request.query.benchmark == b for s in window)
         for b in ("tpch", "tpcds")}
    subqs = {b: sum(s.request.query.n_subqs for s in window
                    if s.request.query.benchmark == b) for b in n}
    for b in n:
        assert tr.counter(f"solve.solved.{b}") == n[b]
        assert tr.counter(f"solve.subqs.{b}") == subqs[b]
        assert tr.counter(f"runtime.requests.{b}") > 0
    assert tr.counter("solve.solved") == sum(n.values())
    assert tr.counter("solve.subqs") == sum(subqs.values())
    assert tr.counter("runtime.requests") == sum(
        tr.counter(f"runtime.requests.{b}") for b in n)
    # The flush that held both benchmarks split its phases by model.
    assert tr.counter("solve.phases") < tr.counter("solve.model_groups") \
        <= 2 * tr.counter("solve.phases")


# -- tune_batch ---------------------------------------------------------------

MIXED = [("tpch", 1), ("tpcds", 3), ("tpch", 5), ("tpcds", 18),
         ("tpch", 9)]


def test_a_mixed_batch_equals_the_single_benchmark_batches(pairs):
    qs = _queries(MIXED)
    with obs.record() as rec:
        mixed = TuningService(model=_maps(pairs, "subq"),
                              cfg=CFG).tune_batch(qs, WEIGHTS)
    for bench in ("tpch", "tpcds"):
        idx = [i for i, q in enumerate(qs) if q.benchmark == bench]
        alone = TuningService(model=pairs[bench]["subq"], cfg=CFG) \
            .tune_batch([qs[i] for i in idx], WEIGHTS)
        for i, ct in zip(idx, alone):
            assert _same_ct(mixed[i], ct), (bench, qs[i].qid)
    assert rec.counter("solve.phases") > 0
    assert rec.counter("solve.model_groups") == \
        2 * rec.counter("solve.phases")


def test_one_model_keeps_the_one_benchmark_path(pairs):
    """A single ``PerfModel`` gives one model group per phase, the
    sequential path's results, and the same results and counters as a
    mapping that holds only that model."""
    qs = _queries([("tpch", 1), ("tpch", 5), ("tpch", 9)])
    m = pairs["tpch"]["subq"]
    got = {}
    # Each run with a model of its own (the same parameters, an empty
    # embedding memo); compile times (``...@<span>``) are left out.
    for name, model in (("single", check._twin(m)),
                        ("mapping", {"tpch": check._twin(m)})):
        with obs.record() as rec:
            got[name] = (TuningService(model=model, cfg=CFG)
                         .tune_batch(qs, WEIGHTS),
                         {k: v for k, v in rec.counters.items()
                          if "@" not in k})
    (single, counters), (mapped, mapped_counters) = got["single"], \
        got["mapping"]
    assert counters == mapped_counters
    assert counters["solve.phases"] == counters["solve.model_groups"] > 0
    assert counters["solve.solved"] == counters["solve.solved.tpch"] == 3
    assert not any(k.endswith(".tpcds") for k in counters)
    twin = check._twin(m)
    for q, a, b in zip(qs, single, mapped):
        ref = compile_time_optimize(q, model=twin, weights=WEIGHTS, cfg=CFG)
        assert _same_ct(a, ref) and _same_ct(b, ref), q.qid


# -- caches and the degraded path --------------------------------------------

def test_replacing_one_models_keeps_the_others_cache_hits(pairs):
    h, ds = _queries([("tpch", 1), ("tpcds", 3)])
    svc = TuningService(model=_maps(pairs, "subq"), cfg=CFG)
    first = svc.tune_batch([h, ds], WEIGHTS)
    retrained = _model("subq", 99)
    assert model_fingerprint(retrained) != \
        model_fingerprint(pairs["tpcds"]["subq"])
    svc.model = {"tpch": pairs["tpch"]["subq"], "tpcds": retrained}
    again = svc.tune_batch([h, ds], WEIGHTS)
    assert (svc.last_batch.n_solved, svc.last_batch.n_deduped) == (1, 1)
    assert again[0] is first[0]
    ref = compile_time_optimize(ds, model=check._twin(retrained),
                                weights=WEIGHTS, cfg=CFG)
    assert _same_ct(again[1], ref)
    # Retiring the TPC-DS model's entries leaves TPC-H's.
    assert svc._results.clear_model(model_fingerprint(retrained)) == 1
    svc.tune_batch([h, ds], WEIGHTS)
    assert (svc.last_batch.n_solved, svc.last_batch.n_deduped) == (1, 1)


def test_the_degraded_path_uses_each_querys_own_model(pairs):
    """Cheap solves on cached banks and default θ, under a mapping, equal
    what a one-benchmark service with that benchmark's model gives."""
    full = _queries([("tpch", 1), ("tpcds", 3)], variant=0)
    variants = _queries([("tpch", 1), ("tpcds", 3)], variant=1)
    unseen = _queries([("tpch", 5), ("tpcds", 18)], variant=1)
    mixed = TuningService(model=_maps(pairs, "subq"), cfg=CFG)
    mixed.tune_batch(full, WEIGHTS)
    cheap = mixed.tune_batch(variants + unseen, WEIGHTS,
                             degraded=[True] * 4)
    assert (mixed.last_batch.n_cheap,
            mixed.last_batch.n_default_theta) == (2, 2)
    for i, q in enumerate(variants + unseen):
        alone = TuningService(model=pairs[q.benchmark]["subq"], cfg=CFG)
        alone.tune_batch([full[i % 2]], WEIGHTS)
        want = alone.tune_batch([q], WEIGHTS, degraded=[True])[0]
        assert _same_ct(cheap[i], want), q.qid
    other = default_theta_result(unseen[1], model=pairs["tpch"]["subq"])
    assert not np.array_equal(cheap[3].front, other.front)
