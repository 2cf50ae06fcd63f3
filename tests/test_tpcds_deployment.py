"""The ``tpcds_sf100`` deployment of the chip benchmark, on the CPU.

* The benchmark's copy of the template generator, read from the
  configuration's ``workload`` group, builds the program's TPC-DS queries
  operator for operator, for all 102 templates.
* Served through ``OptimizerServer`` at the configuration's model widths
  and a small solver, a stream holding both 48-subQ templates, which
  arrive while a runtime session is live, gives every result bit for bit
  as the sequential ``compile_time_optimize`` + ``run_with_aqe`` path does,
  and model outputs within the benchmark check's limits of its plain
  reference.
"""
import json
import os
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, queries, train  # noqa: E402
from chipbench.probes import HeadRecorder  # noqa: E402
from repro.core.models.gtn import GTNConfig  # noqa: E402
from repro.core.models.perf_model import ModelConfig, PerfModel  # noqa: E402
from repro.core.moo.hmooc import HMOOCConfig  # noqa: E402
from repro.queryengine import workloads  # noqa: E402
from repro.queryengine.workloads import StreamRequest  # noqa: E402
from repro.serve import (OptimizerServer, RuntimeSession,  # noqa: E402
                         ServerConfig, ServiceTimeModel, TuningService)

with open(os.path.join(ROOT, "chipbench", "configs", "tpcds_sf100.json")) as f:
    CFG = json.load(f)
WL = CFG["workload"]
LARGEST = (87, 90)            # the two 48-subQ templates
SMALLEST = 3                  # a 3-table template: 6 subQs
# (template, arrival): two queries open a session, the 48-subQ ones and
# others arrive while it is live (the modelled clock charges 0.05 s a
# flush and 0.01 s a round).  Three sizes only (12, 6 and 48 subQs): the
# eager reference compiles its operations once per size.
STREAM = [(0, 0.0), (SMALLEST, 0.0), (LARGEST[0], 0.06), (5, 0.07),
          (LARGEST[1], 0.08), (18, 0.3)]
CLOCK = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)), round_s=0.01)


@pytest.mark.parametrize("variant", [0, 1, 2])
def test_config_builds_the_programs_tpcds_queries(variant):
    assert WL["n_templates"] == 102
    for t in range(WL["n_templates"]):
        got = queries.make_query(WL, t, variant)
        want = workloads.make_query("tpcds", t, variant=variant)
        assert (got.qid, got.benchmark, got.template) == \
            (want.qid, want.benchmark, want.template)
        assert got.ops == want.ops, t
        assert got.subqs == want.subqs, t
    sizes = {t: queries.make_query(WL, t, variant).n_subqs
             for t in range(WL["n_templates"])}
    assert max(sizes.values()) == 48
    assert [t for t, n in sizes.items() if n == 48] == list(LARGEST)
    assert sizes[SMALLEST] == 6


def _models():
    """``subq`` and ``qs`` models at the configuration's widths, with
    seeded random parameters in the layout the reference reads."""
    mc = CFG["model"]
    gtn = GTNConfig(**mc["gtn"])
    out = {}
    for i, kind in enumerate(("subq", "qs")):
        params = train.init_params(jax.random.PRNGKey(11 + i), mc["gtn"],
                                   mc["hidden"], mc["theta_dim"][kind],
                                   mc["n_targets"])
        out[kind] = PerfModel(
            ModelConfig(kind=kind, theta_dim=mc["theta_dim"][kind], gtn=gtn,
                        hidden=tuple(mc["hidden"]),
                        n_targets=mc["n_targets"]), params=params)
    return out


@pytest.fixture(scope="module")
def window():
    models = _models()
    hcfg = HMOOCConfig(**dict(CFG["hmooc"], n_c_init=16, n_p_pool=64,
                              n_c_enrich=16, max_bank=16))
    weights = tuple(CFG["weights"])
    server = OptimizerServer(
        config=ServerConfig(**dict(CFG["server"], clock=CLOCK)),
        tuning=TuningService(model=models["subq"], cfg=hcfg),
        session=RuntimeSession(model_subq=models["subq"],
                               model_qs=models["qs"], weights=weights))
    reqs = [StreamRequest(rid=i, query=queries.make_query(WL, t, 5000 + i),
                          arrival_s=at)
            for i, (t, at) in enumerate(STREAM)]
    heads = {k: HeadRecorder(m, 7 + i)
             for i, (k, m) in enumerate(models.items())}
    try:
        served = server.serve(reqs)
    finally:
        for h in heads.values():
            h.remove()
    return {"served": served, "models": models, "hcfg": hcfg,
            "weights": weights,
            "heads": {k: h.rows_out() for k, h in heads.items()}}


def test_large_requests_join_a_live_session(window):
    served = window["served"]
    assert [s.status for s in served] == ["served"] * len(STREAM)
    by_template = {s.request.query.template: s for s in served}
    for t in LARGEST:
        assert by_template[t].request.query.n_subqs == 48
        assert by_template[t].joined_running, t
    assert not by_template[0].joined_running


def test_every_result_equals_the_sequential_path(window):
    n_bad, bad = check.sequential_diffs(window["served"], window["models"],
                                        window["hcfg"], window["weights"])
    assert n_bad == 0, bad


def test_model_outputs_within_the_checks_limits(window):
    models = window["models"]
    params = {k: m.params for k, m in models.items()}
    stats = {k: m.target_stats for k, m in models.items()}
    refs = check.references(params)
    readings = check.model_gaps(window["served"], models, window["heads"],
                                refs[check.REFERENCE], stats, CFG)
    readings["sequential_diffs"] = 0
    ok, checks = check.verdict(readings)
    assert ok, checks
    assert all(len(window["heads"][k][0]) for k in models)
    # The sampled set of a run always holds the request with the most subQs.
    assert check.sample(window["served"], 3, 2 ** 31 + 5)[0] \
        .request.query.n_subqs == 48
