"""Nonstationary scenario engine + elastic capacity: replay-equivalence.

The tentpole invariant: every scenario is a pure function of its seeds, so
for every (arrival shape × event timeline) scenario in the matrix the
streamed server's *surviving* per-tenant outputs are bit-identical to an
offline one-at-a-time replay under each request's stamped weights — the
golden-determinism contract of PRs 3–6 extended to time-varying arrivals,
mid-stream preference shifts, tenant churn, capacity changes, elastic
batch caps, preemptive degradation, and token-bucket door rejections all
at once.  (Which requests survive at full quality is timing-dependent
under overload; *what* a survivor is served never is.)
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core.moo.hmooc import HMOOCConfig
from repro.queryengine.scenarios import (ARRIVAL_SHAPES, TIMELINES,
                                         CapacityEvent, ScenarioEvent,
                                         ScenarioSpec, scenario_matrix)
from repro.queryengine.workloads import (ArrivalModel, StreamRequest,
                                         TenantSpec, make_query,
                                         serving_stream)
from repro.serve import (CandidatePoolCache, ElasticController,
                         ElasticPolicy, OptimizerServer, RuntimeSession,
                         ServerConfig, ServiceTimeModel, TuningService)

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)
WEIGHTS = (0.9, 0.1)

MATRIX = scenario_matrix(n_per_tenant=4, rate_qps=40.0)


def _same(got, ref):
    np.testing.assert_array_equal(got.theta_p_eff, ref.theta_p_eff)
    np.testing.assert_array_equal(got.theta_s_eff, ref.theta_s_eff)
    np.testing.assert_array_equal(got.final_join, ref.final_join)
    np.testing.assert_array_equal(got.sim.ana_latency, ref.sim.ana_latency)
    np.testing.assert_array_equal(got.sim.actual_latency,
                                  ref.sim.actual_latency)
    np.testing.assert_array_equal(got.sim.io_gb, ref.sim.io_gb)
    np.testing.assert_array_equal(got.sim.cost, ref.sim.cost)


def _offline_replay(served):
    """One-at-a-time offline reference for every full-quality survivor,
    solved under the request's stamped weights (shared exact caches — the
    golden contract says sharing cannot change outputs)."""
    svc = TuningService(cfg=CFG)
    pools = CandidatePoolCache()
    out = {}
    for s in served:
        if s.status != "served":
            continue
        w = tuple(s.request.weights) if s.request.weights is not None \
            else WEIGHTS
        ct = svc.tune_batch([s.request.query], w)[0]
        sess = RuntimeSession(weights=w, pool_cache=pools)
        out[s.rid] = sess.run_batch([s.request.query], [ct])[0]
    return out


# ---------------------------------------------------------------------------
# Tentpole: golden replay-equivalence across the full scenario matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", MATRIX, ids=[m.name for m in MATRIX])
def test_replay_equivalence_matrix(spec):
    """Streamed serve (elastic capacity + capacity events + rate limits +
    SLO triage all armed) vs offline one-at-a-time replay: surviving
    outputs bit-identical per request, including across preference-shift
    and churn boundaries."""
    sc = spec.build(seed=2)
    srv = OptimizerServer(
        config=ServerConfig(max_batch=4,
                            elastic=ElasticPolicy(max_batch=16)),
        weights=WEIGHTS, cfg=CFG, tenants=sc.tenants)
    served = srv.serve(sc.requests, capacity_events=sc.capacity_events)
    assert len(served) == len(sc.requests)
    assert all(s.status in ("served", "degraded", "shed", "rate_limited")
               for s in served)
    survivors = [s for s in served if s.status == "served"]
    assert survivors, "scenario served nothing at full quality"
    ref = _offline_replay(served)
    for s in survivors:
        _same(s.result, ref[s.rid])
    # Rejected requests never produced a plan; everything else did.
    for s in served:
        if s.status in ("shed", "rate_limited"):
            assert s.result is None and s.ct is None
        else:
            assert s.result is not None
            assert math.isfinite(s.finished_s)


def test_pref_shift_replays_identically_on_both_sides():
    """The stale-θ regression at matrix scale: a scenario whose tenants
    flip latency↔cost preferences mid-stream replays bit-identically on
    *both* sides of the shift boundary."""
    spec = [m for m in MATRIX if m.name == "diurnal-pref_shift"][0]
    sc = spec.build(seed=5)
    shift_at = min(e.at_s for e in spec.events)
    # A deterministic charged clock guarantees survivors on both sides of
    # the shift regardless of host timing (measured wall charges can shed
    # a whole side of the boundary on a slow run).
    clock = ServiceTimeModel(flush_points=((1, 0.005), (4, 0.01)),
                             round_s=0.0005)
    srv = OptimizerServer(config=ServerConfig(max_batch=4, clock=clock),
                          weights=WEIGHTS, cfg=CFG, tenants=sc.tenants)
    served = srv.serve(sc.requests)
    pre = [s for s in served if s.status == "served"
           and s.arrival_s < shift_at]
    post = [s for s in served if s.status == "served"
            and s.arrival_s >= shift_at]
    assert pre and post, "need survivors on both sides of the shift"
    ref = _offline_replay(served)
    for s in pre + post:
        _same(s.result, ref[s.rid])


# ---------------------------------------------------------------------------
# Scenario builds: seed-purity, event semantics
# ---------------------------------------------------------------------------

def _fingerprint(sc):
    return [(r.rid, r.tenant, r.arrival_s, r.query.qid, r.weights)
            for r in sc.requests]


@pytest.mark.parametrize("name", [m.name for m in MATRIX])
def test_scenario_build_is_seed_pure(name):
    spec = [m for m in MATRIX if m.name == name][0]
    a, b = spec.build(seed=3), spec.build(seed=3)
    assert _fingerprint(a) == _fingerprint(b)
    assert a.capacity_events == b.capacity_events
    assert [t.name for t in a.tenants] == [t.name for t in b.tenants]
    other = spec.build(seed=4)
    assert _fingerprint(a) != _fingerprint(other)
    times = [r.arrival_s for r in a.requests]
    assert times == sorted(times)
    assert [r.rid for r in a.requests] == list(range(len(a.requests)))


def test_weight_shift_stamped_per_request():
    spec = [m for m in MATRIX if m.name == "ramp-pref_shift"][0]
    sc = spec.build(seed=2)
    by_ev = {e.tenant: e for e in spec.events}
    for tname, ev in by_ev.items():
        orig = [t for t in spec.tenants if t.name == tname][0].weights
        for r in sc.requests:
            if r.tenant != tname:
                continue
            want = ev.weights if r.arrival_s >= ev.at_s else orig
            assert r.weights == want, (r.rid, r.arrival_s)


def test_churn_join_leave_semantics():
    spec = [m for m in MATRIX if m.name == "flash_crowd-churn"][0]
    sc = spec.build(seed=2)
    join_at = [e.at_s for e in spec.events if e.kind == "join"][0]
    leave_at = [e.at_s for e in spec.events if e.kind == "leave"][0]
    joiner = [r for r in sc.requests if r.tenant == "joiner"]
    leaver = [r for r in sc.requests if r.tenant == "be"]
    assert joiner and all(r.arrival_s >= join_at for r in joiner)
    assert all(r.arrival_s < leave_at for r in leaver)
    assert "joiner" in [t.name for t in sc.tenants]
    assert sc.capacity_events == tuple(sorted(
        (CapacityEvent(e.at_s, e.max_batch) for e in spec.events
         if e.kind == "capacity"), key=lambda c: c.at_s))


def test_scenario_validation():
    t = TenantSpec(name="a")
    with pytest.raises(ValueError, match="unknown event kind"):
        ScenarioEvent(at_s=0.0, kind="bogus")
    with pytest.raises(ValueError, match="tenant= and weights="):
        ScenarioEvent(at_s=0.0, kind="weights", tenant="a")
    with pytest.raises(ValueError, match="needs spec"):
        ScenarioEvent(at_s=0.0, kind="join")
    with pytest.raises(ValueError, match="!= spec name"):
        ScenarioEvent(at_s=0.0, kind="join", tenant="b", spec=t)
    with pytest.raises(ValueError, match="needs tenant"):
        ScenarioEvent(at_s=0.0, kind="leave")
    with pytest.raises(ValueError, match="max_batch"):
        ScenarioEvent(at_s=0.0, kind="capacity", max_batch=0)
    with pytest.raises(ValueError, match="finite"):
        ScenarioEvent(at_s=math.inf, kind="leave", tenant="a")
    with pytest.raises(ValueError, match="at least one tenant"):
        ScenarioSpec(name="x")
    with pytest.raises(ValueError, match="duplicate tenant"):
        ScenarioSpec(name="x", tenants=(t,), events=(
            ScenarioEvent(at_s=0.0, kind="join", spec=TenantSpec(name="a")),))
    with pytest.raises(ValueError, match="unknown tenant"):
        ScenarioSpec(name="x", tenants=(t,), events=(
            ScenarioEvent(at_s=0.0, kind="leave", tenant="ghost"),))


# ---------------------------------------------------------------------------
# Nonstationary arrival models
# ---------------------------------------------------------------------------

def test_nonstationary_arrival_kinds_reproducible_and_sorted():
    for kind in ("diurnal", "spike", "ramp"):
        m = ArrivalModel(kind=kind, rate_qps=20.0)
        a, b = m.draw(64, seed=7), m.draw(64, seed=7)
        np.testing.assert_array_equal(a, b)
        assert (np.diff(a) >= 0).all()
        assert a.shape == (64,) and a[0] >= 0.0
        assert not np.array_equal(a, m.draw(64, seed=8))


def test_spike_concentrates_arrivals_in_the_window():
    m = ArrivalModel(kind="spike", rate_qps=5.0, spike_at_s=2.0,
                     spike_dur_s=2.0, spike_factor=8.0)
    t = m.draw(400, seed=1)
    hot = ((t >= 2.0) & (t < 4.0)).sum()
    # 2 s at 40 qps ≈ 80 arrivals vs 5 qps elsewhere.
    pre = (t < 2.0).sum()
    assert hot > 3 * pre
    assert m.rate_at(3.0) == pytest.approx(40.0)
    assert m.rate_at(1.0) == pytest.approx(5.0)
    assert m.rate_at(4.0) == pytest.approx(5.0)   # half-open window


def test_diurnal_rate_curve_and_bounds():
    m = ArrivalModel(kind="diurnal", rate_qps=10.0, period_s=40.0,
                     amplitude=0.5)
    assert m.rate_at(0.0) == pytest.approx(10.0)
    assert m.rate_at(10.0) == pytest.approx(15.0)   # sin peak at T/4
    assert m.rate_at(30.0) == pytest.approx(5.0)    # trough at 3T/4
    t = m.draw(200, seed=3)
    assert (np.diff(t) >= 0).all()
    # Instantaneous rate stays within the envelope used for thinning.
    for x in np.linspace(0.0, 80.0, 41):
        assert 0.0 < m.rate_at(float(x)) <= m._max_rate() + 1e-12


def test_ramp_rate_holds_after_ramp():
    m = ArrivalModel(kind="ramp", rate_qps=4.0, ramp_to_qps=16.0,
                     ramp_dur_s=2.0)
    assert m.rate_at(0.0) == pytest.approx(4.0)
    assert m.rate_at(1.0) == pytest.approx(10.0)
    assert m.rate_at(2.0) == pytest.approx(16.0)
    assert m.rate_at(50.0) == pytest.approx(16.0)   # holds, no overshoot


def test_nonstationary_validation():
    with pytest.raises(ValueError, match="amplitude"):
        ArrivalModel(kind="diurnal", amplitude=1.0).draw(3)
    with pytest.raises(ValueError, match="period_s"):
        ArrivalModel(kind="diurnal", period_s=0.0).draw(3)
    with pytest.raises(ValueError, match="spike_factor"):
        ArrivalModel(kind="spike", spike_factor=0.0).draw(3)
    with pytest.raises(ValueError, match="ramp_to_qps"):
        ArrivalModel(kind="ramp", ramp_to_qps=-1.0).rate_at(0.0)


# ---------------------------------------------------------------------------
# Stale-weight regression: a shift never serves a stale-weight θ
# ---------------------------------------------------------------------------

def test_weight_shift_never_serves_stale_theta():
    """The same query on both sides of a preference shift: the post-shift
    request must be a fresh solve under the new weights (the ResponseCache
    key carries the weights — a stale hit would be a cache-key bug), and
    each side bit-matches its own offline solve."""
    q = make_query("tpch", 8, variant=1)
    reqs = [StreamRequest(rid=0, query=q, arrival_s=0.0, tenant="t",
                          weights=(0.99, 0.01)),
            StreamRequest(rid=1, query=q, arrival_s=0.05, tenant="t",
                          weights=(0.01, 0.99))]
    srv = OptimizerServer(
        config=ServerConfig(max_batch=1), weights=WEIGHTS, cfg=CFG,
        tenants=[TenantSpec(name="t", weights=(0.99, 0.01))])
    served = srv.serve(reqs)
    # Two solves, zero cross-boundary hits: the shift key-misses the cache.
    assert srv.tuning._results.misses == 2
    assert srv.tuning._results.hits == 0
    pre, post = served
    assert pre.ct.choice != post.ct.choice or not np.array_equal(
        pre.ct.theta_c, post.ct.theta_c)
    for s, w in ((pre, (0.99, 0.01)), (post, (0.01, 0.99))):
        ref = TuningService(cfg=CFG).tune_batch([q], w)[0]
        assert s.ct.choice == ref.choice
        np.testing.assert_array_equal(s.ct.theta_c, ref.theta_c)
    # Replaying the same shifted stream hits the cache per-side — the
    # weights dimension separates the entries, it doesn't disable reuse.
    srv.serve(reqs)
    assert srv.tuning._results.hits == 2


# ---------------------------------------------------------------------------
# Elastic capacity control + capacity events
# ---------------------------------------------------------------------------

def test_capacity_events_bound_flush_sizes():
    stream = serving_stream("tpch", 12, seed=11,
                            arrivals=ArrivalModel(kind="poisson",
                                                  rate_qps=60.0))
    srv = OptimizerServer(config=ServerConfig(max_batch=6),
                          weights=WEIGHTS, cfg=CFG)
    served = srv.serve(stream, capacity_events=[(0.0, 2), (0.15, 6)])
    assert all(s.result is not None for s in served)
    st = srv.last_run
    assert len(st.flush_caps) == len(st.flush_windows) >= 2
    for (_, n), cap in zip(st.flush_windows, st.flush_caps):
        assert n <= cap
    assert min(st.flush_caps) == 2          # the dip actually applied
    # Outputs unchanged by the capacity dance (golden contract).
    queries = [r.query for r in stream]
    cts = TuningService(cfg=CFG).tune_batch(queries, WEIGHTS)
    ref = RuntimeSession(weights=WEIGHTS).run_batch(queries, cts)
    for s, r in zip(served, ref):
        _same(s.result, r)


def test_elastic_controller_raises_cap_under_pressure():
    """A burst at t=0 with a tiny base cap: the queue-delay forecast rises
    while solving, so the elastic cap must exceed the base cap at some
    flush — and survivors still bit-match offline."""
    stream = [dataclasses.replace(r, arrival_s=0.0)
              for r in serving_stream("tpch", 12, seed=9,
                                      arrivals=ArrivalModel(rate_qps=40.0))]
    srv = OptimizerServer(
        config=ServerConfig(
            max_batch=2, admit_mid_session=False,
            elastic=ElasticPolicy(max_batch=8, target_delay_s=0.01,
                                  ewma=1.0)),
        weights=WEIGHTS, cfg=CFG)
    served = srv.serve(stream)
    assert all(s.result is not None for s in served)
    assert max(srv.last_run.flush_caps) > 2
    queries = [r.query for r in stream]
    cts = TuningService(cfg=CFG).tune_batch(queries, WEIGHTS)
    ref = RuntimeSession(weights=WEIGHTS).run_batch(queries, cts)
    for s, r in zip(served, ref):
        _same(s.result, r)


LOADED = scenario_matrix(n_per_tenant=4, rate_qps=7.0)


@pytest.mark.parametrize("spec", LOADED, ids=[m.name for m in LOADED])
def test_elastic_capacity_no_worse_than_static(spec):
    """On a modelled clock (no host jitter), steady load at about 0.7x the
    static cap's capacity with the scenario's peaks past it: the elastic
    controller, allowed twice the static cap, loses no goodput, and its
    strict-tenant p99 stays within the static one or the budget."""
    budget = 0.3
    clock = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)),
                             round_s=0.005, cheap_s=0.001)
    sc = spec.build(seed=0)
    common = dict(max_batch=2, solve_budget_s=budget, solve_reserve_s=0.07,
                  clock=clock)

    def serve(config):
        srv = OptimizerServer(config=config, weights=WEIGHTS, cfg=CFG,
                              tenants=sc.tenants)
        served = srv.serve(sc.requests, capacity_events=sc.capacity_events)
        return srv.latency_report(served), srv.last_run.flush_caps

    st, _ = serve(ServerConfig(**common))
    el, caps = serve(ServerConfig(
        **common, elastic=ElasticPolicy(min_batch=2, max_batch=4,
                                        target_delay_s=0.25 * budget)))
    assert st["goodput"] < 1.0                  # the load overloads static
    if spec.name.startswith("flash_crowd"):
        assert max(caps) > 2    # the elastic cap (or a capacity event) lifts
    assert el["goodput"] >= st["goodput"]
    st_p99 = st["tenants"]["strict"]["plan_latency_s"]["p99"]
    el_p99 = el["tenants"]["strict"]["plan_latency_s"]["p99"]
    if math.isfinite(st_p99) and math.isfinite(el_p99):
        assert el_p99 <= max(st_p99, budget)


def test_preemptive_degradation_engages_before_deadline():
    """With elastic control and a saturated forecast, a degrade-class head
    whose budget is *not yet* blown is still routed to the cheap path when
    the forecast headroom is gone (the PR-5 next-step: degrade before the
    budget blows, not at the post-mortem)."""
    from repro.serve import TenantScheduler
    sched = TenantScheduler(
        [TenantSpec(name="d", slo="degrade", solve_budget_s=1.0)],
        reserve_q_s=0.2)
    sched.enqueue("d", "x", 0.0)
    # At t=0.3 with E[n]=1: deadline = 0+1.0−0.2 = 0.8 → meetable now, so
    # plain compose serves it at full quality...
    assert sched.compose(0.3, cap=4) == [("d", "x", False)]
    # ...but with a 0.6 s lead (forecast pressure), the same head degrades.
    sched.enqueue("d", "y", 0.0)
    assert sched.compose(0.3, cap=4, degrade_lead_s=0.6) == \
        [("d", "y", True)]


def test_elastic_policy_validation():
    with pytest.raises(ValueError, match="min_batch"):
        ElasticPolicy(min_batch=4, max_batch=2)
    with pytest.raises(ValueError, match="target_delay_s"):
        ElasticPolicy(target_delay_s=0.0)
    with pytest.raises(ValueError, match="ewma"):
        ElasticPolicy(ewma=0.0)
    with pytest.raises(ValueError, match="degrade_frac"):
        ElasticPolicy(degrade_frac=1.5)
    ctl = ElasticController(ElasticPolicy(max_batch=8))
    assert ctl.batch_cap(4) == 4                     # no pressure: base cap


# ---------------------------------------------------------------------------
# Deterministic charged-time model (ServiceTimeModel)
# ---------------------------------------------------------------------------

def test_clock_model_interpolates_and_validates():
    m = ServiceTimeModel(flush_points=((8, 0.08), (2, 0.02), (4, 0.04)),
                         round_s=0.001)
    assert m.flush_points == ((2, 0.02), (4, 0.04), (8, 0.08))  # sorted
    assert m.flush_s(3) == pytest.approx(0.03)       # interior interpolation
    assert m.flush_s(16) == pytest.approx(0.16)      # extrapolate last seg
    assert m.flush_s(1) == pytest.approx(0.01)       # extrapolate first seg
    assert ServiceTimeModel(flush_points=((4, 0.1),)).flush_s(99) == 0.1
    # Extrapolation below the first knot clamps at zero, never negative.
    down = ServiceTimeModel(flush_points=((4, 0.01), (8, 0.5)))
    assert down.flush_s(1) == 0.0
    # Cheap members (cache hits / degraded paths) are priced at cheap_s,
    # not on the solve curve; the full-solve remainder interpolates as
    # usual, and an all-cheap flush costs no solve at all.
    c = ServiceTimeModel(flush_points=((2, 0.02), (4, 0.04)), cheap_s=0.001)
    assert c.flush_s(4, n_cheap=1) == pytest.approx(0.03 + 0.001)
    assert c.flush_s(4, n_cheap=4) == pytest.approx(0.004)
    assert c.flush_s(4, n_cheap=99) == pytest.approx(0.004)   # clamped to n
    assert c.flush_s(4, n_cheap=-3) == c.flush_s(4)           # clamped to 0
    with pytest.raises(ValueError, match="finite"):
        ServiceTimeModel(flush_points=((1, 0.1),), cheap_s=-0.1)
    with pytest.raises(ValueError, match="at least one knot"):
        ServiceTimeModel(flush_points=())
    with pytest.raises(ValueError, match="unique"):
        ServiceTimeModel(flush_points=((2, 0.1), (2, 0.2)))
    with pytest.raises(ValueError, match=">= 1"):
        ServiceTimeModel(flush_points=((0, 0.1),))
    with pytest.raises(ValueError, match="finite"):
        ServiceTimeModel(flush_points=((1, math.nan),))
    with pytest.raises(ValueError, match="finite"):
        ServiceTimeModel(flush_points=((1, 0.1),), round_s=-1.0)


def test_clock_model_makes_the_admission_timeline_deterministic():
    """With a ServiceTimeModel charged instead of measured wall time, two
    serves of the same scenario agree on *everything* — statuses, flush
    sizes and caps, charged windows, and every per-request lifecycle
    timestamp — not just on outputs.  (This is what lets the scenario
    benchmark compare elastic vs static capacity free of host jitter.)"""
    spec = [m for m in MATRIX if m.name == "flash_crowd-churn"][0]
    sc = spec.build(seed=9)
    clock = ServiceTimeModel(flush_points=((1, 0.01), (4, 0.03), (16, 0.1)),
                             round_s=0.002, cheap_s=0.0005)
    cfgv = ServerConfig(max_batch=4, solve_budget_s=0.5, clock=clock,
                        elastic=ElasticPolicy(min_batch=4, max_batch=16,
                                              target_delay_s=0.1))

    def once():
        srv = OptimizerServer(config=cfgv, weights=WEIGHTS, cfg=CFG,
                              tenants=sc.tenants)
        served = srv.serve(sc.requests, capacity_events=sc.capacity_events)
        st = srv.last_run
        return ([(s.rid, s.status, s.admitted_s, s.compiled_s, s.finished_s)
                 for s in served],
                list(st.flush_windows), list(st.flush_caps))

    a, b = once(), once()
    # NaN-tolerant exact comparison (rejected requests carry NaN stamps).
    assert repr(a) == repr(b)
    # Every charged flush window is exactly the model's for *some* split
    # of the batch into full solves and cheap members, none measured.
    for w, size in a[1]:
        assert any(w == clock.flush_s(size, n_cheap=k)
                   for k in range(size + 1))


# ---------------------------------------------------------------------------
# Token-bucket rate limiting, end to end
# ---------------------------------------------------------------------------

def test_rate_limited_requests_door_rejected_deterministically():
    """Fixed arrivals at 4× the tenant's sustained rate with burst 1: the
    bucket admits exactly every 4th arrival; rejections are first-class
    outcomes (never enqueued, never solved) and the pattern is a pure
    function of the stream — identical across servers."""
    spec = TenantSpec(name="rl", weights=WEIGHTS, rate_limit_qps=5.0,
                      rate_limit_burst=1.0,
                      arrivals=ArrivalModel(kind="fixed", rate_qps=20.0))
    stream = [dataclasses.replace(r, tenant="rl")
              for r in serving_stream("tpch", 8, seed=21,
                                      arrivals=spec.arrivals)]

    def run():
        srv = OptimizerServer(config=ServerConfig(max_batch=4),
                              weights=WEIGHTS, cfg=CFG, tenants=[spec])
        return srv, srv.serve(stream)

    srv, served = run()
    statuses = [s.status for s in served]
    assert statuses == ["served", "rate_limited", "rate_limited",
                        "rate_limited"] * 2
    for s in served:
        if s.status == "rate_limited":
            assert s.ct is None and s.result is None
            assert s.finished_s == s.arrival_s
    assert srv.last_run.n_rate_limited == 6
    assert srv.scheduler.state("rl").n_rate_limited == 6
    assert srv.scheduler.state("rl").n_enqueued == 2
    rep = srv.latency_report(served)
    assert rep["n_rate_limited"] == 6
    assert rep["rate_limited_rate"] == pytest.approx(0.75)
    assert rep["n_finished"] == 2
    assert rep["goodput"] <= 0.25
    # Deterministic across servers (bucket clocked by arrivals, not wall).
    _, served2 = run()
    assert [s.status for s in served2] == statuses


def test_rate_limit_spec_validation():
    with pytest.raises(ValueError, match="rate_limit_qps"):
        TenantSpec(name="x", rate_limit_qps=0.0)
    with pytest.raises(ValueError, match="rate_limit_burst"):
        TenantSpec(name="x", rate_limit_qps=1.0, rate_limit_burst=0.5)


# ---------------------------------------------------------------------------
# Windowed latency report (satellite: phase-resolved metrics)
# ---------------------------------------------------------------------------

def test_windowed_report_partitions_and_separates_phases():
    spec = [m for m in MATRIX if m.name == "flash_crowd-steady"][0]
    sc = spec.build(seed=6)
    srv = OptimizerServer(config=ServerConfig(max_batch=4),
                          weights=WEIGHTS, cfg=CFG, tenants=sc.tenants)
    served = srv.serve(sc.requests)
    span = (max(s.arrival_s for s in served)
            - min(s.arrival_s for s in served))
    rep = srv.latency_report(served, window_s=span / 4 + 1e-9)
    ws = rep["windows"]
    assert len(ws) >= 2
    assert sum(w["n_arrived"] for w in ws) == len(served)
    assert sum(w["n_finished"] for w in ws) == rep["n_finished"]
    assert sum(w["n_shed"] for w in ws) == rep["n_shed"]
    for a, b in zip(ws, ws[1:]):
        assert b["t0_s"] == pytest.approx(a["t1_s"])
    for w in ws:
        if w["n_finished"]:
            assert math.isfinite(w["plan_latency_s"]["p99"])
            assert 0.0 <= w["goodput"] <= 1.0
    with pytest.raises(ValueError, match="window_s"):
        srv.latency_report(served, window_s=0.0)


def test_report_counts_follow_the_sample_not_the_run():
    """Regression (this PR): every count/rate in the report derives from
    the ``served`` argument, so a report over a slice (one tenant, one
    phase) is internally consistent — the old ``n_queries`` came from the
    whole last run and silently mixed samples."""
    spec = [m for m in MATRIX if m.name == "diurnal-steady"][0]
    sc = spec.build(seed=7)
    srv = OptimizerServer(config=ServerConfig(max_batch=4),
                          weights=WEIGHTS, cfg=CFG, tenants=sc.tenants)
    served = srv.serve(sc.requests)
    sub = [s for s in served if s.tenant == "deg"]
    rep = srv.latency_report(sub)
    assert rep["n_queries"] == len(sub) != len(served)
    assert rep["n_shed"] == sum(1 for s in sub if s.status == "shed")
    assert rep["n_finished"] <= len(sub)
