"""The batched compile-time solve: parity with the reference + compile bounds.

``TuningService.tune_batch`` fuses every (query, subQ, candidate) stage
evaluation of a micro-batch into bucket-padded ``PerfModel.predict_rows``
dispatches (per-request evaluation on the oracle backend) and drives the
HMOOC solves in lockstep.  These tests pin its two contracts:

* **bit identity** — per-query results, cache statistics and stored
  artifacts are exactly those of a per-query ``compile_time_optimize`` loop
  over one shared effective-set cache, including dedup, template reuse,
  per-tenant keying and degraded-query interleaving, under both backends;
* **bounded recompilation** — across arbitrarily varying batch sizes the
  jitted functions compile at most one signature per shape bucket.
"""
import numpy as np
import pytest

from repro import obs
from repro.core.moo.hmooc import HMOOCConfig
from repro.core.tuning.compile_time import (compile_time_optimize,
                                            default_theta_result)
from repro.core.tuning.objectives import StageObjectives, fused_stage_eval
from repro.queryengine.simulator import DEFAULT_COST
from repro.queryengine.workloads import make_benchmark, serving_stream
from repro.serve import EffectiveSetCache, TuningService
from repro.serve.cache import query_fingerprint

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)
WEIGHTS = (0.9, 0.1)


def _assert_ct_equal(a, b):
    np.testing.assert_array_equal(a.front, b.front)
    assert a.choice == b.choice
    np.testing.assert_array_equal(a.theta_c, b.theta_c)
    np.testing.assert_array_equal(a.theta_p_sub, b.theta_p_sub)
    np.testing.assert_array_equal(a.theta_s_sub, b.theta_s_sub)
    np.testing.assert_array_equal(a.theta_p0, b.theta_p0)
    np.testing.assert_array_equal(a.theta_s0, b.theta_s0)


def _stats_tuple(svc):
    s = svc.last_batch
    return (s.n_queries, s.n_solved, s.n_deduped, s.n_cheap,
            s.n_default_theta)


def _reference(queries, model, weights=None, tenants=None, degraded=None):
    """Per-query ``compile_time_optimize`` over one shared effective-set cache.

    The response dedup is written out from the request list: a request
    identical to an earlier one (tenant, query, statistics, weights) takes
    the earlier result.  A degraded request with no earlier full solve runs
    on the template's cached banks, else on the Spark defaults.  Returns
    ``(results, cache, stats tuple, response hits)``.
    """
    n = len(queries)
    weights = weights or [WEIGHTS] * n
    cache = EffectiveSetCache()
    done, out = {}, []
    solved = cheap = default = hits = 0
    for i, q in enumerate(queries):
        w = weights[i]
        key = (tenants[i] if tenants else None, q.qid, query_fingerprint(q),
               w)
        if key in done:
            out.append(done[key])
            hits += 1
        elif degraded and degraded[i]:
            if ("degraded",) + key in done:
                out.append(done[("degraded",) + key])
                hits += 1
                continue
            peeked = cache.peek(q, CFG, model, DEFAULT_COST)
            if peeked is None:
                res = default_theta_result(q, model=model)
                default += 1
            else:
                res = compile_time_optimize(q, model=model, weights=w,
                                            cfg=CFG,
                                            effective_set=peeked[0])
                cheap += 1
            done[key if peeked is not None and peeked[1]
                 else ("degraded",) + key] = res
            out.append(res)
        else:
            done[key] = compile_time_optimize(q, model=model, weights=w,
                                              cfg=CFG, cache=cache)
            out.append(done[key])
            solved += 1
    return out, cache, (n, solved, n - solved - cheap - default, cheap,
                        default), hits


@pytest.mark.parametrize("backend", ["model", "oracle"])
def test_solve_bitmatches_reference(smoke_perf_models, backend):
    """Repeated-template stream: per-query results, response dedup and
    effective-set reuse all match the per-query reference bit for bit, for
    the model-backed service and the oracle (``model=None``) one."""
    model = smoke_perf_models["subq"] if backend == "model" else None
    stream = serving_stream("tpch", 10, seed=5)   # repeats templates
    ref, cache, stats, hits = _reference(stream, model)
    svc = TuningService(model=model, cfg=CFG)
    got = svc.tune_batch(stream)
    for a, b in zip(ref, got):
        _assert_ct_equal(a, b)
    assert stats[2] > 0                   # the stream does repeat requests
    assert _stats_tuple(svc) == stats
    assert svc.cache.stats() == cache.stats()
    assert svc._results.stats()["hits"] == hits
    # Second identical batch: fully deduped.
    again = svc.tune_batch(stream)
    assert svc.last_batch.n_deduped == len(stream)
    for a, b in zip(got, again):
        _assert_ct_equal(a, b)


def test_solve_per_tenant_golden_determinism(smoke_perf_models):
    """Per-tenant keys and per-query weights survive the batched path
    unchanged: each tenant gets the pick its own weights select, identical
    to a sequential solve of the same request."""
    model = smoke_perf_models["subq"]
    qs = make_benchmark("tpch")
    queries = [qs[1], qs[1], qs[5]]
    tenants = ["a", "b", "a"]
    weights = [(1.0, 0.0), (0.0, 1.0), (0.5, 0.5)]
    ref, cache, stats, _ = _reference(queries, model, weights, tenants)
    svc = TuningService(model=model, cfg=CFG)
    got = svc.tune_batch(queries, weights, tenants=tenants)
    for a, b in zip(ref, got):
        _assert_ct_equal(a, b)
    assert _stats_tuple(svc) == stats
    assert svc.cache.stats() == cache.stats()
    # Same front across weights, picks chosen per request's own weights.
    np.testing.assert_array_equal(got[0].front, got[1].front)
    assert got[0].chosen_objectives[0] <= got[1].chosen_objectives[0]


@pytest.mark.parametrize("backend", ["model", "oracle"])
@pytest.mark.parametrize("degraded", [
    [False, True, False, False, True, False, False, False],
    # t000-v1 on t000-v0's banks (cheap), t009-v0 after its full solve (hit)
    [False, True, False, False, False, True, False, True],
], ids=["defaults", "cheap-and-hit"])
def test_solve_degraded_interleave_matches_reference(smoke_perf_models,
                                                     backend, degraded):
    """Degraded queries act as barriers inside a batch; stats and results
    still match the per-query transcript exactly."""
    model = smoke_perf_models["subq"] if backend == "model" else None
    stream = serving_stream("tpch", 8, seed=11)
    ref, cache, stats, hits = _reference(stream, model, degraded=degraded)
    svc = TuningService(model=model, cfg=CFG)
    got = svc.tune_batch(stream, degraded=degraded)
    for a, b in zip(ref, got):
        _assert_ct_equal(a, b)
    assert _stats_tuple(svc) == stats
    assert svc.cache.stats() == cache.stats()
    assert svc._results.stats()["hits"] == hits


def test_solve_recompilation_bound():
    """Across varying micro-batch sizes the jitted model functions compile
    one signature per shape bucket: the compiles counted under the model's
    dispatch span equal the buckets it used."""
    from test_serve import _tiny_perf_model
    model = _tiny_perf_model(seed=2)
    svc = TuningService(model=model, cfg=CFG, dedupe=False)
    stream = serving_stream("tpch", 12, seed=3)
    with obs.record() as rec:
        for size in (1, 3, 2, 5, 1):
            batch, stream = stream[:size], stream[size:]
            svc.tune_batch(batch)
    stats = model.compile_stats()
    assert rec.counter("compiles@repro.model.dispatch.subq") == \
        len(stats["head_buckets"]) + len(stats["embed_buckets"])


def test_default_theta_result_batched_equivalence(smoke_perf_models):
    """Satellite: the vectorized degraded fallback equals the historical
    per-subQ loop (model-backed).  One batched regressor dispatch replaces
    m batch-of-one calls; XLA's matvec-vs-matmul codegen may differ in the
    final float32 ulp, so equivalence is to float32 precision — the
    reduction order itself is unchanged (left-to-right over subQs)."""
    model = smoke_perf_models["subq"]
    q = make_benchmark("tpch")[2]
    res = default_theta_result(q, model=model)
    obj = StageObjectives(q, model=model)
    tc_u = obj.cs.default_unit()[None, :]
    tps_u = np.tile(np.concatenate([obj.ps.default_unit(),
                                    obj.ss.default_unit()]), (obj.m, 1))
    front = np.zeros((1, 2), np.float64)
    for i in range(obj.m):
        front[0] += obj.stage_eval(i, tc_u, tps_u[i:i + 1])[0]
    np.testing.assert_allclose(res.front, front, rtol=2e-6)
    assert res.n_evals == q.n_subqs
    # Determinism: repeated batched evaluations are bit-identical.
    res2 = default_theta_result(q, model=model)
    np.testing.assert_array_equal(res.front, res2.front)


def test_fused_stage_eval_matches_per_request(smoke_perf_models):
    """fused_stage_eval row slices equal the per-request stage_eval calls
    they replace, across queries and subQs in one dispatch."""
    model = smoke_perf_models["subq"]
    qs = make_benchmark("tpch")
    rng = np.random.default_rng(0)
    items, refs = [], []
    for q in (qs[1], qs[5]):
        obj = StageObjectives(q, model=model)
        for i in range(min(2, obj.m)):
            n = int(rng.integers(3, 9))
            Tc = rng.random((n, obj.d_c))
            Tps = rng.random((n, obj.d_ps))
            items.append((obj, i, Tc, Tps))
            refs.append(obj.stage_eval(i, Tc, Tps))
    got = fused_stage_eval(items)
    assert len(got) == len(refs)
    for g, r in zip(got, refs):
        np.testing.assert_array_equal(g, r)


def test_embedding_independent_of_dispatch_size(smoke_perf_models):
    """A subQ embedded alone equals its row of a many-graph embed_many
    dispatch (the served micro-batch prefetch vs the per-query solve)."""
    from repro.core.models.perf_model import PerfModel
    model = smoke_perf_models["subq"]

    def twin():
        return PerfModel(model.cfg, params=model.params,
                         target_stats=model.target_stats)

    qs = make_benchmark("tpch")
    pairs = [(q, i) for q in qs[:6] for i in range(q.n_subqs)]
    batched = twin()
    batched.embed_many(pairs)
    alone = twin()
    for q, i in pairs[:12]:
        np.testing.assert_array_equal(alone.embed(q, i),
                                      batched.embed(q, i))
    # One dispatch shape, however many graphs a call brings.
    assert batched.embed_buckets == alone.embed_buckets
    assert len(alone.embed_buckets) == 1


def test_fused_stage_eval_oracle_fallback():
    """Oracle backend (model=None) falls back to per-request evaluation."""
    q = make_benchmark("tpch")[1]
    obj = StageObjectives(q)
    Tc = np.full((4, obj.d_c), 0.5)
    Tps = np.full((4, obj.d_ps), 0.5)
    got = fused_stage_eval([(obj, 0, Tc, Tps), (obj, 1, Tc, Tps)])
    np.testing.assert_array_equal(got[0], obj.stage_eval(0, Tc, Tps))
    np.testing.assert_array_equal(got[1], obj.stage_eval(1, Tc, Tps))


@pytest.mark.parametrize("bench, qi", [("tpch", 2), ("tpcds", 87)])
def test_plan_rows_per_candidate_bitmatch(smoke_perf_models, bench, qi):
    """A model-backed HmoocPlan at the default config, driven phase by
    phase through fused_stage_eval: every phase's outputs equal the
    per-request stage_eval on the expanded rows bit for bit, the θc
    conversion runs once per distinct candidate of the phase (not per row),
    and the final front and θ equal hmooc_solve's."""
    from repro.core.moo.hmooc import HmoocPlan, hmooc_solve
    model = smoke_perf_models["subq"]
    q = make_benchmark(bench)[qi]
    if bench == "tpcds":
        assert q.n_subqs == 48
    cfg = HMOOCConfig()
    obj = StageObjectives(q, model=model)
    plan = HmoocPlan(obj.m, obj.d_c, obj.d_ps, cfg, snap_c=obj.snap_c,
                     snap_ps=obj.snap_ps)
    phases = 0
    while not plan.done:
        reqs = plan.requests()
        cands = {id(rows.cands): rows.cands.shape[0] for _, rows in reqs}
        n_rows = sum(rows.cidx.shape[0] for _, rows in reqs)
        with obs.record() as rec:
            got = fused_stage_eval([(obj, i, rows) for i, rows in reqs])
        assert rec.counter("model.rows.subq") == n_rows
        assert rec.counter("solve.cost_rows") == sum(cands.values())
        assert len(cands) == 1
        expect = (plan.eset.Uc if plan.banks_ready else plan.eset.reps)
        assert sum(cands.values()) == expect.shape[0] < n_rows
        for (i, rows), F in zip(reqs, got):
            np.testing.assert_array_equal(
                F, obj.stage_eval(i, rows.Tc, rows.Tps))
        plan.feed(got)
        phases += 1
    assert phases == 2
    ref = hmooc_solve(obj.stage_eval, obj.m, obj.d_c, obj.d_ps, cfg,
                      snap_c=obj.snap_c, snap_ps=obj.snap_ps)
    np.testing.assert_array_equal(plan.result.front, ref.front)
    np.testing.assert_array_equal(plan.result.theta_c, ref.theta_c)
    np.testing.assert_array_equal(plan.result.theta_ps, ref.theta_ps)
    assert plan.result.n_evals == ref.n_evals


def test_fused_stage_eval_plain_rows_count_each_row(smoke_perf_models):
    """Plain (θc, θp⊕θs) rows are every row its own candidate: the cost
    counter then equals the rows dispatched."""
    model = smoke_perf_models["subq"]
    obj = StageObjectives(make_benchmark("tpch")[1], model=model)
    rng = np.random.default_rng(1)
    items = [(obj, i, rng.random((5 + i, obj.d_c)),
              rng.random((5 + i, obj.d_ps))) for i in range(2)]
    with obs.record() as rec:
        fused_stage_eval(items)
    assert rec.counter("solve.cost_rows") == \
        rec.counter("model.rows.subq") == 11


def test_plan_builds_assign_rows_once(smoke_perf_models, monkeypatch):
    """A served solve builds its assign phase's rows once: feed() scatters
    with the chunks requests() kept, not a rebuild."""
    from repro.core.moo import hmooc as hmooc_mod
    calls = []
    orig = hmooc_mod._assign_requests

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(hmooc_mod, "_assign_requests", spy)
    svc = TuningService(model=smoke_perf_models["subq"], cfg=CFG)
    svc.tune_batch([make_benchmark("tpch")[3]])
    assert svc.last_batch.n_solved == 1
    assert len(calls) == 1


def _per_bank_rep_banks(Fs, eset, cfg):
    """_rep_banks as a loop of _pareto_bank over every (subQ, rep) bank."""
    from repro.core.moo.hmooc import _pareto_bank
    C, P = eset.reps.shape[0], eset.pool.shape[0]
    opt_idx = [[] for _ in range(C)]
    for F in Fs:
        Fr = F.reshape(C, P, F.shape[1])
        for r in range(C):
            opt_idx[r].append(_pareto_bank(Fr[r], cfg.max_bank))
    return opt_idx, Fs[-1].shape[1], sum(F.shape[0] for F in Fs)


def test_served_banks_batched_match_per_bank(smoke_perf_models, monkeypatch):
    """A model-backed served solve of a 48-subQ TPC-DS request and a TPC-H
    request at the default config filters every bank in the vectorized
    pass, and its banks and results equal those of the per-bank loop bit
    for bit.  With the Pareto kernel's row threshold below the pool size
    the banks go bank by bank through pareto_mask_fast's kernel route."""
    from repro.core.moo import hmooc as hmooc_mod
    from repro.core.moo import pareto as pareto_mod
    queries = [make_benchmark("tpcds")[87], make_benchmark("tpch")[2]]
    assert queries[0].n_subqs == 48
    cfg = HMOOCConfig()

    def solve():
        banks = []
        orig = hmooc_mod._rep_banks

        def spy(Fs, eset, cfg):
            out = orig(Fs, eset, cfg)
            banks.append(out[0])
            return out

        with monkeypatch.context() as mp:
            mp.setattr(hmooc_mod, "_rep_banks", spy)
            svc = TuningService(model=smoke_perf_models["subq"], cfg=cfg)
            with obs.record() as rec:
                got = svc.tune_batch(queries)
        assert svc.last_batch.n_solved == len(queries)
        assert len(banks) == len(queries)
        return got, banks, rec

    def assert_same(a, b):
        for x, y in zip(a[0], b[0]):
            _assert_ct_equal(x, y)
            assert x.n_evals == y.n_evals
        for x, y in zip(a[1], b[1]):
            for xr, yr in zip(x, y, strict=True):
                for xi, yi in zip(xr, yr, strict=True):
                    assert xi.dtype == yi.dtype
                    np.testing.assert_array_equal(xi, yi)

    batched = solve()
    subqs = sum(q.n_subqs for q in queries)
    assert batched[2].counter("solve.subqs") == subqs
    assert batched[2].counter("hmooc.banks.batched") == \
        cfg.n_clusters * subqs

    with monkeypatch.context() as mp:
        mp.setattr(hmooc_mod, "_rep_banks", _per_bank_rep_banks)
        per_bank = solve()
    assert per_bank[2].counter("hmooc.banks.batched") == 0
    assert_same(batched, per_bank)

    kernel_rows = []

    def kernel(F, valid=None):      # the float64 mask, on the kernel's route
        kernel_rows.append(F.shape[0])
        return pareto_mod.pareto_mask_np(F, valid)

    with monkeypatch.context() as mp:
        mp.setattr(pareto_mod, "_KERNEL_MIN_N", cfg.n_p_pool // 2)
        mp.setattr(pareto_mod, "_pareto_mask_kernel", kernel)
        routed = solve()
    assert routed[2].counter("hmooc.banks.batched") == 0
    assert kernel_rows.count(cfg.n_p_pool) > 0
    assert_same(batched, routed)
