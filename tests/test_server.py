"""Streaming-admission server: golden determinism, lifecycle, budget smoke.

The golden invariant (acceptance criterion): on the oracle backend the
``OptimizerServer`` output — final plans and objective values — is
bit-identical to the offline ``tune_batch`` → ``RuntimeSession.run_batch``
pipeline for the same workload, however the stream is sliced into
micro-batches and admission epochs.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.moo.hmooc import HMOOCConfig
from repro.queryengine.workloads import (ArrivalModel, StreamRequest,
                                         TenantSpec, make_query,
                                         multi_tenant_stream, serving_stream)
from repro.serve import (OptimizerServer, RuntimeSession, ServerConfig,
                         ServiceTimeModel, TuningService)

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)
WEIGHTS = (0.9, 0.1)
N_STREAM = 14


@pytest.fixture(scope="module")
def timed_stream():
    return serving_stream("tpch", N_STREAM, seed=1,
                          arrivals=ArrivalModel(kind="poisson",
                                                rate_qps=40.0))


@pytest.fixture(scope="module")
def offline(timed_stream):
    """The batch-path reference: all queries at once through both halves."""
    queries = [r.query for r in timed_stream]
    cts = TuningService(cfg=CFG).tune_batch(queries, WEIGHTS)
    res = RuntimeSession(weights=WEIGHTS).run_batch(queries, cts)
    return cts, res


def _server(max_batch, **cfg_kw):
    return OptimizerServer(config=ServerConfig(max_batch=max_batch, **cfg_kw),
                           weights=WEIGHTS, cfg=CFG)


def _assert_same_outputs(served, offline_results):
    for s, ref in zip(served, offline_results):
        got = s.result
        np.testing.assert_array_equal(got.theta_p_eff, ref.theta_p_eff)
        np.testing.assert_array_equal(got.theta_s_eff, ref.theta_s_eff)
        np.testing.assert_array_equal(got.final_join, ref.final_join)
        np.testing.assert_array_equal(got.sim.ana_latency, ref.sim.ana_latency)
        np.testing.assert_array_equal(got.sim.actual_latency,
                                      ref.sim.actual_latency)
        np.testing.assert_array_equal(got.sim.io_gb, ref.sim.io_gb)
        np.testing.assert_array_equal(got.sim.cost, ref.sim.cost)
        assert got.requests_sent == ref.requests_sent
        assert got.requests_total == ref.requests_total


# ---------------------------------------------------------------------------
# Golden end-to-end determinism
# ---------------------------------------------------------------------------

def test_server_one_at_a_time_matches_batch_path(timed_stream, offline):
    _, ref = offline
    served = _server(max_batch=1).serve(timed_stream)
    _assert_same_outputs(served, ref)


def test_server_micro_batches_match_batch_path(timed_stream, offline):
    _, ref = offline
    served = _server(max_batch=4).serve(timed_stream)
    _assert_same_outputs(served, ref)


def test_server_shuffled_micro_batches_match(timed_stream, offline):
    """Shuffle which micro-batch each query lands in (permute the arrival
    stamps); per-rid outputs must not move."""
    _, ref = offline
    rng = np.random.default_rng(7)
    times = np.sort([r.arrival_s for r in timed_stream])
    perm = rng.permutation(len(timed_stream))
    shuffled = sorted(
        (dataclasses.replace(r, arrival_s=float(times[perm[i]]))
         for i, r in enumerate(timed_stream)),
        key=lambda r: r.arrival_s)
    served = _server(max_batch=5).serve(shuffled)
    by_rid = {s.rid: s for s in served}
    _assert_same_outputs([by_rid[r.rid] for r in timed_stream], ref)


def test_mid_session_admission_matches_batch_path(timed_stream, offline):
    """Force late arrivals into a running session: everything arrives at
    t=0 except a tail that lands mid-flight; outputs still bit-match."""
    _, ref = offline
    reqs = [dataclasses.replace(r, arrival_s=0.0 if r.rid < 10 else 1e-4)
            for r in timed_stream]
    srv = _server(max_batch=10, solve_reserve_s=0.0)
    served = srv.serve(reqs)
    by_rid = {s.rid: s for s in served}
    _assert_same_outputs([by_rid[r.rid] for r in timed_stream], ref)
    # The tail actually joined a live session (not a fresh batch).
    assert srv.last_run.n_joined_running >= 1
    assert any(s.joined_running for s in served)


def test_repeat_serve_shares_caches(timed_stream, offline):
    """A long-lived server keeps amortizing: a second identical stream is
    served entirely from the response cache (zero new solves) and returns
    identical results."""
    _, ref = offline
    srv = _server(max_batch=4)
    first = srv.serve(timed_stream)
    _assert_same_outputs(first, ref)
    solved_before = srv.tuning._results.misses
    second = srv.serve(timed_stream)
    _assert_same_outputs(second, ref)
    assert srv.tuning._results.misses == solved_before
    # Candidate pools were drawn exactly once across both epochs.
    assert srv.session.pool_cache.misses == 1


@pytest.mark.parametrize("shape", ["gaps_over_budget", "burst_of_20"])
def test_idle_server_flushes_at_once(shape):
    """Work-conserving admission: a request that finds the server idle is
    admitted at its arrival, and a burst flushes in batches of at most
    ``max_batch``; results equal the offline reference either way."""
    n = 20 if shape == "burst_of_20" else 6
    gap = 0.0 if shape == "burst_of_20" else 3.0      # budget is 1.0 s
    reqs = [StreamRequest(rid=i, query=q, arrival_s=i * gap)
            for i, q in enumerate(serving_stream("tpch", n, seed=4))]
    clock = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)),
                             round_s=0.01)
    srv = _server(max_batch=8, clock=clock)
    served = srv.serve(reqs)
    queries = [r.query for r in reqs]
    ref = RuntimeSession(weights=WEIGHTS).run_batch(
        queries, TuningService(cfg=CFG).tune_batch(queries, WEIGHTS))
    _assert_same_outputs(served, ref)
    st = srv.last_run
    sizes = [k for _, k in st.flush_windows]
    assert sum(sizes) == n and max(sizes) <= 8
    flushes = {r: st.trace.counter("admission.flush." + r)
               for r in ("idle", "full", "session")}
    assert sum(flushes.values()) == st.n_micro_batches
    if shape == "gaps_over_budget":
        assert all(s.admitted_s == s.arrival_s for s in served)
        assert flushes["idle"] == n and sizes == [1] * n
    else:
        assert sizes[0] == 8 and flushes["full"] >= 1
        assert all(s.busy_wait_s == pytest.approx(s.admitted_s - s.arrival_s)
                   for s in served)


# ---------------------------------------------------------------------------
# Lifecycle / scheduling behavior
# ---------------------------------------------------------------------------

def test_server_latency_accounting(timed_stream):
    srv = _server(max_batch=4)
    served = srv.serve(timed_stream)
    rep = srv.latency_report(served)
    assert rep["n_queries"] == len(timed_stream)
    assert rep["n_micro_batches"] >= math.ceil(len(timed_stream) / 4)
    for s in served:
        assert s.arrival_s <= s.admitted_s <= s.compiled_s <= s.finished_s
    assert rep["plan_latency_s"]["p50"] > 0.0
    assert rep["plan_latency_s"]["max"] >= rep["plan_latency_s"]["p99"] >= \
        rep["plan_latency_s"]["p50"]


def test_deadline_flush_beats_full_batch(timed_stream):
    """With max_batch larger than the stream, only the solve-budget
    deadline can flush; every query must still be served."""
    srv = _server(max_batch=64, solve_budget_s=0.05, solve_reserve_s=0.0)
    served = srv.serve(timed_stream)
    assert all(s.result is not None for s in served)
    assert srv.last_run.n_micro_batches >= 1


def test_serve_refuses_foreign_active_session(timed_stream, offline):
    cts, _ = offline
    srv = _server(max_batch=4)
    srv.session.admit(timed_stream[0].query, cts[0])   # outside the server
    with pytest.raises(RuntimeError, match="idle session"):
        srv.serve(timed_stream)


def test_server_rejects_conflicting_construction(timed_stream):
    sess = RuntimeSession(weights=(0.9, 0.1))
    with pytest.raises(ValueError, match="conflicts"):
        OptimizerServer(session=sess, weights=(0.5, 0.5))
    # Matching weights alongside a prebuilt session are accepted.
    OptimizerServer(session=sess, weights=(0.9, 0.1))
    with pytest.raises(ValueError, match="not both"):
        OptimizerServer(tuning=TuningService(cfg=CFG), cfg=CFG)


def test_serve_rejects_duplicate_rids(timed_stream):
    dup = list(timed_stream) + [timed_stream[0]]
    with pytest.raises(ValueError, match="duplicate rids"):
        _server(max_batch=4).serve(dup)


def test_serve_and_report_handle_empty_stream():
    srv = _server(max_batch=4)
    assert srv.serve([]) == []
    rep = srv.latency_report([])
    assert rep["n_queries"] == 0
    assert math.isnan(rep["plan_latency_s"]["p99"])


def test_run_batch_refuses_active_session(timed_stream, offline):
    cts, _ = offline
    sess = RuntimeSession(weights=WEIGHTS)
    sess.admit(timed_stream[0].query, cts[0])
    with pytest.raises(RuntimeError, match="active"):
        sess.run_batch([timed_stream[1].query], [cts[1]])


def test_session_join_retire_interleaved(timed_stream, offline):
    """Drive the open-set lifecycle by hand: admit half, run one round,
    admit the rest, drain; per-query results equal the closed-batch run."""
    cts, ref = offline
    queries = [r.query for r in timed_stream]
    sess = RuntimeSession(weights=WEIGHTS)
    half = len(queries) // 2
    entries = [sess.admit(q, ct) for q, ct in
               zip(queries[:half], cts[:half])]
    sess.step_round()
    entries += [sess.admit(q, ct) for q, ct in
                zip(queries[half:], cts[half:])]
    while sess.step_round():
        pass
    retired = sess.retire_ready()
    assert sess.n_active == 0 and len(retired) == len(queries)
    results = sess.realize(entries)   # realize in admission order
    for got, want in zip(results, ref):
        np.testing.assert_array_equal(got.theta_p_eff, want.theta_p_eff)
        np.testing.assert_array_equal(got.final_join, want.final_join)
        np.testing.assert_array_equal(got.sim.cost, want.sim.cost)


# ---------------------------------------------------------------------------
# Arrival-model reproducibility (satellite: explicit arrival-time model)
# ---------------------------------------------------------------------------

def test_arrival_model_reproducible_and_sorted():
    a1 = serving_stream("tpch", 16, seed=5,
                        arrivals=ArrivalModel(kind="poisson", rate_qps=8.0))
    a2 = serving_stream("tpch", 16, seed=5,
                        arrivals=ArrivalModel(kind="poisson", rate_qps=8.0))
    assert all(isinstance(r, StreamRequest) for r in a1)
    assert [r.arrival_s for r in a1] == [r.arrival_s for r in a2]
    assert [r.query.qid for r in a1] == [r.query.qid for r in a2]
    times = [r.arrival_s for r in a1]
    assert times == sorted(times) and times[0] > 0.0
    # Different seed ⇒ different timing; same model kind keeps the mean rate.
    b = serving_stream("tpch", 16, seed=6,
                       arrivals=ArrivalModel(kind="poisson", rate_qps=8.0))
    assert [r.arrival_s for r in b] != times


def test_arrival_model_kinds():
    fixed = ArrivalModel(kind="fixed", rate_qps=4.0).draw(5, seed=0)
    np.testing.assert_allclose(np.diff(fixed), 0.25)
    uni = ArrivalModel(kind="uniform", rate_qps=4.0).draw(200, seed=0)
    assert (np.diff(uni) >= 0).all() and np.diff(uni).max() <= 0.5 + 1e-12
    with pytest.raises(ValueError):
        ArrivalModel(kind="bogus").draw(3)
    with pytest.raises(ValueError):
        ArrivalModel(rate_qps=0.0).draw(3)


# ---------------------------------------------------------------------------
# Multi-tenant golden determinism (oracle backend)
# ---------------------------------------------------------------------------

def test_multi_tenant_per_tenant_parity_oracle():
    """N tenants with different preferences and arrival rates sharing one
    server: each tenant's output is bit-identical to the offline pipeline
    solved under that tenant's own weights — fairness shapes latency, never
    plans."""
    from repro.queryengine.workloads import TenantSpec, multi_tenant_stream
    specs = [TenantSpec(name="lat", weights=(0.9, 0.1), share=2.0,
                        arrivals=ArrivalModel(kind="poisson", rate_qps=30.0)),
             TenantSpec(name="bal", weights=(0.5, 0.5), priority=1,
                        arrivals=ArrivalModel(kind="poisson", rate_qps=20.0)),
             TenantSpec(name="cost", weights=(0.1, 0.9),
                        arrivals=ArrivalModel(kind="uniform", rate_qps=10.0))]
    reqs = multi_tenant_stream("tpch", specs, 4, seed=8)
    srv = OptimizerServer(config=ServerConfig(max_batch=4), weights=WEIGHTS,
                          cfg=CFG, tenants=specs)
    served = srv.serve(reqs)
    for spec in specs:
        sub = [s for s in served if s.tenant == spec.name]
        assert len(sub) == 4
        queries = [s.request.query for s in sub]
        cts = TuningService(cfg=CFG).tune_batch(queries, spec.weights)
        ref = RuntimeSession(weights=spec.weights).run_batch(queries, cts)
        _assert_same_outputs(sub, ref)


def test_tenant_weights_actually_change_picks():
    """Identical query served to latency-heavy and cost-heavy tenants must
    be solved under each tenant's own weights (equal picks would mean the
    preference vector was dropped somewhere along the path)."""
    import dataclasses as _dc
    from repro.queryengine.workloads import TenantSpec, make_query
    q = make_query("tpch", 8, variant=1)
    specs = [TenantSpec(name="lat", weights=(0.99, 0.01)),
             TenantSpec(name="cost", weights=(0.01, 0.99))]
    reqs = [StreamRequest(rid=0, query=q, arrival_s=0.0, tenant="lat"),
            StreamRequest(rid=1, query=q, arrival_s=0.0, tenant="cost")]
    srv = OptimizerServer(config=ServerConfig(max_batch=2), weights=WEIGHTS,
                          cfg=CFG, tenants=specs)
    served = srv.serve(reqs)
    lat, cost = served[0], served[1]
    assert lat.ct.choice != cost.ct.choice or not np.array_equal(
        lat.ct.theta_c, cost.ct.theta_c)
    # Each matches its own offline solve exactly.
    for s, w in ((lat, (0.99, 0.01)), (cost, (0.01, 0.99))):
        ref = TuningService(cfg=CFG).tune_batch([q], w)[0]
        assert s.ct.choice == ref.choice
        np.testing.assert_array_equal(s.ct.theta_c, ref.theta_c)


# ---------------------------------------------------------------------------
# Overload: shedding / degrading never perturbs surviving queries (oracle)
# ---------------------------------------------------------------------------

def _overload_specs():
    """Three SLO classes; strict/degrade budgets are unmeetable by
    construction (budget 0 < any positive reserve), so triage decisions
    are deterministic even though solve times are measured wall time."""
    return [
        TenantSpec(name="strict", slo="strict", solve_budget_s=0.0,
                   arrivals=ArrivalModel(kind="poisson", rate_qps=50.0)),
        TenantSpec(name="deg", slo="degrade", solve_budget_s=0.0,
                   arrivals=ArrivalModel(kind="poisson", rate_qps=50.0)),
        TenantSpec(name="be", slo="best_effort", weights=(0.5, 0.5),
                   arrivals=ArrivalModel(kind="poisson", rate_qps=50.0)),
    ]


def test_overload_shed_degrade_survivors_bit_identical():
    """Overloaded mixed-SLO stream: the strict tenant sheds everything
    (budget 0), the degrade tenant resolves via the cheap path, and every
    *surviving* full-quality query still bit-matches the offline pipeline
    under its tenant's weights — shedding/degrading shapes who gets served,
    never what the survivors are served."""
    specs = _overload_specs()
    reqs = multi_tenant_stream("tpch", specs, 5, seed=13)
    srv = OptimizerServer(config=ServerConfig(max_batch=4), weights=WEIGHTS,
                          cfg=CFG, tenants=specs)
    served = srv.serve(reqs)
    by = {name: [s for s in served if s.tenant == name]
          for name in ("strict", "deg", "be")}
    # Strict: all shed, first-class outcomes, nothing solved.
    assert [s.status for s in by["strict"]] == ["shed"] * 5
    assert all(s.ct is None and s.result is None for s in by["strict"])
    assert all(math.isfinite(s.finished_s) for s in by["strict"])
    assert srv.last_run.n_shed == 5
    # Degrade: all admitted via the cheap path, and they did resolve.
    assert [s.status for s in by["deg"]] == ["degraded"] * 5
    assert all(s.result is not None for s in by["deg"])
    assert srv.last_run.n_degraded == 5
    # Best-effort absorbed the queueing at full quality...
    assert [s.status for s in by["be"]] == ["served"] * 5
    # ...and its outputs bit-match the offline pipeline under its weights.
    queries = [s.request.query for s in by["be"]]
    cts = TuningService(cfg=CFG).tune_batch(queries, (0.5, 0.5))
    ref = RuntimeSession(weights=(0.5, 0.5)).run_batch(queries, cts)
    _assert_same_outputs(by["be"], ref)
    # Scheduler accounting matches the served statuses.
    assert srv.scheduler.state("strict").n_shed == 5
    assert srv.scheduler.state("deg").n_degraded == 5
    assert srv.last_run.tenant_slots == {"deg": 5, "be": 5}


MODEL_CLOCK = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)),
                               round_s=0.005, cheap_s=0.001)


def test_strict_tenant_keeps_its_solve_budget_under_overload():
    """On a modelled clock, one tenant per SLO class past the server's
    capacity: the strict tenant sheds part of its load, every strict
    request it serves is compiled inside its budget, and the best-effort
    tenant absorbs the queueing and is served whole."""
    budget = 0.5
    specs = [TenantSpec(name=slo, slo=slo, weights=w,
                        solve_budget_s=(10 * budget if slo == "best_effort"
                                        else budget),
                        priority=int(slo == "strict"),
                        arrivals=ArrivalModel(kind="poisson", rate_qps=20.0))
             for slo, w in (("strict", (0.9, 0.1)), ("degrade", (0.7, 0.3)),
                            ("best_effort", (0.5, 0.5)))]
    reqs = multi_tenant_stream("tpch", specs, 6, seed=0)
    srv = OptimizerServer(
        config=ServerConfig(max_batch=4, solve_budget_s=budget,
                            clock=MODEL_CLOCK),
        weights=WEIGHTS, cfg=CFG, tenants=specs)
    served = srv.serve(reqs)
    strict = [s for s in served if s.tenant == "strict"]
    kept = [s for s in strict if s.status == "served"]
    assert kept and len(kept) < len(strict)
    assert {s.status for s in strict} == {"served", "shed"}
    assert all(s.compiled_s - s.arrival_s <= budget for s in kept)
    assert all(s.status == "served" for s in served
               if s.tenant == "best_effort")


def test_tenant_tails_stay_fair_on_modelled_clock():
    """Three tenants of unequal share and priority at one aggregate rate:
    on a modelled clock the Jain index over their p99 plan latencies stays
    at or above 0.5 (no tenant starved)."""
    specs = [TenantSpec(name=f"t{i}", weights=w,
                        arrivals=ArrivalModel(kind="poisson",
                                              rate_qps=40.0 / 3),
                        share=2.0 if i == 0 else 1.0, priority=int(i == 1))
             for i, w in enumerate(((0.9, 0.1), (0.7, 0.3), (0.5, 0.5)))]
    reqs = multi_tenant_stream("tpch", specs, [6, 5, 5], seed=0)
    srv = OptimizerServer(
        config=ServerConfig(max_batch=4, solve_budget_s=2.0,
                            clock=MODEL_CLOCK),
        weights=WEIGHTS, cfg=CFG, tenants=specs)
    rep = srv.latency_report(srv.serve(reqs))
    assert all(rep["tenants"][s.name]["n_finished"] > 0 for s in specs)
    assert rep["fairness_jain"] >= 0.5


def test_degraded_path_never_runs_fresh_algorithm1(monkeypatch):
    """Zero fresh Algorithm 1 bank builds for degraded queries: with a warm
    template cache the banks are reused across variants; with a cold cache
    the Spark-default θ is served — `_rep_banks`, which every bank build
    (sequential or batched) goes through, must not run either way."""
    from repro.core.moo import hmooc as hmooc_mod
    spec = TenantSpec(name="deg", slo="degrade", solve_budget_s=0.0,
                      arrivals=ArrivalModel(kind="poisson", rate_qps=50.0))
    reqs = multi_tenant_stream("tpch", [spec], 6, seed=14)
    srv = OptimizerServer(config=ServerConfig(max_batch=3), weights=WEIGHTS,
                          cfg=CFG, tenants=[spec])
    calls = []
    orig = hmooc_mod._rep_banks

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(hmooc_mod, "_rep_banks", spy)
    served = srv.serve(reqs)
    assert [s.status for s in served] == ["degraded"] * 6
    assert all(s.result is not None for s in served)
    assert not calls, "degraded solve triggered a fresh Algorithm 1 run"
    # Cold cache ⇒ at least one request fell back to the Spark defaults.
    assert srv.tuning.cache.stats()["peek_misses"] >= 1

    # Now warm the template cache with full solves of the same queries and
    # serve the degraded stream again: cheap solves reuse the banks — and
    # still zero fresh Algorithm 1 runs for the degraded traffic.
    monkeypatch.setattr(hmooc_mod, "_rep_banks", orig)
    queries = list({s.request.query.qid: s.request.query
                    for s in served}.values())
    srv.tuning.tune_batch(queries, WEIGHTS)          # full-quality warmup
    monkeypatch.setattr(hmooc_mod, "_rep_banks", spy)
    srv2_reqs = multi_tenant_stream("tpch", [spec], 6, seed=14)
    served2 = srv.serve(srv2_reqs)
    assert [s.status for s in served2] == ["degraded"] * 6
    assert not calls
    assert srv.tuning.cache.stats()["peek_hits"] >= 1


def test_degraded_exact_bank_reuse_matches_full_solve():
    """A degraded request whose template banks were computed from the
    *identical* query reuses them exactly: the cheap result equals the
    full solve bit for bit (the degrade path costs quality only across
    variants / cold caches)."""
    from repro.queryengine.workloads import make_query
    q = make_query("tpch", 4, variant=1)
    svc = TuningService(cfg=CFG)
    full = svc.tune_batch([q], WEIGHTS)[0]
    cheap = svc.tune_batch([q], WEIGHTS, degraded=[True])[0]
    # (The exact response cache may serve it directly; either way the
    # degraded result must be the full-quality one.)
    np.testing.assert_array_equal(cheap.front, full.front)
    assert cheap.choice == full.choice
    np.testing.assert_array_equal(cheap.theta_c, full.theta_c)
    np.testing.assert_array_equal(cheap.theta_p_sub, full.theta_p_sub)

    # And through a *fresh* service sharing only the effective-set cache
    # (no response cache hit): exact bank reuse, still bit-identical.
    svc2 = TuningService(cfg=CFG, cache=svc.cache)
    cheap2 = svc2.tune_batch([q], WEIGHTS, degraded=[True])[0]
    np.testing.assert_array_equal(cheap2.front, full.front)
    assert cheap2.choice == full.choice
    np.testing.assert_array_equal(cheap2.theta_c, full.theta_c)


def test_degraded_approx_results_never_served_to_full_requests():
    """Approximate degraded results live under a degrade-marked response
    key: a later full-quality request for the same (query, weights) must
    get a fresh exact solve, not the cross-variant approximation."""
    from repro.queryengine.workloads import make_query
    svc = TuningService(cfg=CFG)
    base = make_query("tpch", 4, variant=1)
    variant = make_query("tpch", 4, variant=2)
    svc.tune_batch([base], WEIGHTS)                   # warm template banks
    cheap = svc.tune_batch([variant], WEIGHTS, degraded=[True])[0]
    assert svc.last_batch.n_cheap == 1
    full = svc.tune_batch([variant], WEIGHTS)[0]
    assert svc.last_batch.n_solved == 1               # not served the approx
    ref = TuningService(cfg=CFG).tune_batch([variant], WEIGHTS)[0]
    np.testing.assert_array_equal(full.front, ref.front)
    assert full.choice == ref.choice
    # The approximation is reused for later degraded requests, though.
    again = svc.tune_batch([variant], WEIGHTS, degraded=[True])[0]
    np.testing.assert_array_equal(again.front, cheap.front)


def test_latency_report_mixed_finished_and_shed():
    """One shed query must not NaN-poison the report (PR-5 bugfix):
    percentiles and Jain aggregate over finished queries only, with shed
    counts reported alongside."""
    specs = [TenantSpec(name="strict", slo="strict", solve_budget_s=0.0,
                        arrivals=ArrivalModel(kind="poisson", rate_qps=40.0)),
             TenantSpec(name="be",
                        arrivals=ArrivalModel(kind="poisson", rate_qps=40.0))]
    reqs = multi_tenant_stream("tpch", specs, 4, seed=15)
    srv = OptimizerServer(config=ServerConfig(max_batch=4), weights=WEIGHTS,
                          cfg=CFG, tenants=specs)
    rep = srv.latency_report(srv.serve(reqs))
    assert rep["n_shed"] == 4 and rep["n_finished"] == 4
    assert rep["shed_rate"] == pytest.approx(0.5)
    for k in ("p50", "p99", "max", "mean"):
        assert math.isfinite(rep["plan_latency_s"][k])
        assert math.isfinite(rep["solve_latency_s"][k])
    assert math.isfinite(rep["fairness_jain"])        # strict tenant dropped
    assert 0.0 < rep["fairness_jain"] <= 1.0
    per = rep["tenants"]
    assert per["strict"]["n_shed"] == 4
    assert per["strict"]["goodput"] == 0.0
    assert math.isnan(per["strict"]["plan_latency_s"]["p99"])
    assert per["be"]["n_shed"] == 0
    assert math.isfinite(per["be"]["plan_latency_s"]["p99"])
    assert rep["goodput"] <= 0.5


def test_makespan_and_qps_ignore_rejection_timestamps():
    """A late rejection must not stretch the makespan (PR-9 bugfix): a
    shed request's finished_s is a rejection timestamp, not service, so a
    tail-shed stream whose last event is a rejection keeps the qps of the
    work actually served."""
    clock = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)),
                             round_s=0.005, cheap_s=0.001)
    specs = [TenantSpec(name="strict", slo="strict", solve_budget_s=0.0),
             TenantSpec(name="be")]
    reqs = [StreamRequest(rid=i, query=make_query("tpch", i, variant=1),
                          arrival_s=0.0, tenant="be") for i in range(4)]
    reqs.append(StreamRequest(rid=4, query=make_query("tpch", 4, variant=1),
                              arrival_s=1000.0, tenant="strict"))
    srv = OptimizerServer(config=ServerConfig(max_batch=4, clock=clock),
                          weights=WEIGHTS, cfg=CFG, tenants=specs)
    served = srv.serve(reqs)
    assert [s.status for s in served] == ["served"] * 4 + ["shed"]
    assert served[-1].finished_s >= 1000.0             # rejection stamped
    st = srv.last_run
    assert st.n_finished == 4 and st.n_shed == 1
    last_served = max(s.finished_s for s in served[:4])
    assert st.makespan_s == pytest.approx(last_served)  # first arrival 0.0
    assert st.makespan_s < 100.0                        # not 1000+
    assert st.qps == pytest.approx(4 / st.makespan_s)
    assert srv.latency_report(served)["qps"] == st.qps


def test_service_time_model_worker_dimension():
    """Fleet co-location contention: every charged cost scales by the
    worker_scale multiplier at n_workers, and with_workers() re-prices
    the same calibration without touching it."""
    base = ServiceTimeModel(flush_points=((1, 0.1), (8, 0.4)), round_s=0.01,
                            cheap_s=0.002, worker_scale=((1, 1.0), (4, 1.25)))
    assert base.worker_mult() == pytest.approx(1.0)
    assert base.with_workers(2).worker_mult() == pytest.approx(1.0 + 0.25 / 3)
    four = base.with_workers(4)
    assert four.worker_mult() == pytest.approx(1.25)
    assert four.flush_s(1) == pytest.approx(base.flush_s(1) * 1.25)
    assert four.flush_s(4, 2) == pytest.approx(base.flush_s(4, 2) * 1.25)
    assert four.round_cost_s() == pytest.approx(base.round_s * 1.25)
    assert four.flush_points == base.flush_points       # calibration intact
    assert four.with_workers(1) == base                 # idempotent re-price
    # The single-knot default means no contention at any width.
    flat = ServiceTimeModel(flush_points=((1, 0.1),))
    assert flat.with_workers(8).flush_s(1) == pytest.approx(flat.flush_s(1))


def test_service_time_model_worker_validation():
    with pytest.raises(ValueError, match="worker-count knots"):
        ServiceTimeModel(flush_points=((1, 0.1),),
                         worker_scale=((1, 1.0), (1, 2.0)))
    with pytest.raises(ValueError, match="worker-count knots"):
        ServiceTimeModel(flush_points=((1, 0.1),), worker_scale=((0, 1.0),))
    with pytest.raises(ValueError, match="multipliers"):
        ServiceTimeModel(flush_points=((1, 0.1),), worker_scale=((1, 0.0),))
    with pytest.raises(ValueError, match="n_workers"):
        ServiceTimeModel(flush_points=((1, 0.1),)).with_workers(0)


def test_jain_index_ignores_nonfinite():
    from repro.serve import jain_index
    assert jain_index([1.0, 1.0, math.nan]) == pytest.approx(1.0)
    assert jain_index([2.0, math.inf, 2.0]) == pytest.approx(1.0)
    assert math.isnan(jain_index([math.nan]))
    assert math.isnan(jain_index([]))
    assert jain_index([1.0, 3.0]) == pytest.approx(16 / (2 * 10))


def test_query_seed_threads_through():
    base = serving_stream("tpch", 8, seed=2)
    same = serving_stream("tpch", 8, seed=2, query_seed=0)
    other = serving_stream("tpch", 8, seed=2, query_seed=9)
    assert [q.qid for q in base] == [q.qid for q in same]
    # Same template/variant choices, different query population.
    fp = lambda qs: [tuple(sq.out_rows for sq in q.subqs) for q in qs]
    assert fp(base) != fp(other)
