"""Fairness/starvation properties of multi-tenant admission.

Property sweeps (via ``_hypothesis_compat``: real hypothesis when
installed, fixed-seed sweep otherwise) drive :class:`TenantScheduler`
directly with synthetic items — no solver in the loop — so adversarial
tenant mixes are cheap to explore:

* conservation — per-tenant batch-slot accounting sums exactly to every
  batch's size, nothing is lost or double-counted, per-tenant FIFO order
  is preserved;
* no starvation — whatever the priority/share mix, a tenant's head
  request is composed into the very next batch once its deadline passes
  (overdue promotion outranks priority tiers), so no tenant waits
  unboundedly while another flushes;
* weighted fairness — deficit-round-robin long-run batch shares track the
  configured share ratios;
* the per-query reserve EWMA regression (PR-4 bugfix): one large batch
  must not inflate the deadline reserve applied to subsequent small
  batches.

Server-level tests then check single-tenant traffic through the
multi-tenant machinery reproduces the anonymous PR-3 path bit-identically,
and that mixed-tenant streams serve every request with conserved
accounting.
"""
import math

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.moo.hmooc import HMOOCConfig
from repro.queryengine.workloads import (ArrivalModel, StreamRequest,
                                         TenantSpec, multi_tenant_stream,
                                         serving_stream)
from repro.serve import (OptimizerServer, RuntimeSession, ServerConfig,
                         TenantScheduler, TuningService)

import dataclasses

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)
WEIGHTS = (0.9, 0.1)


def _random_specs(rng, n_tenants):
    return [TenantSpec(name=f"t{i}",
                       share=float(rng.choice([0.5, 1.0, 2.0, 3.0])),
                       priority=int(rng.integers(0, 3)),
                       solve_budget_s=float(rng.choice([0.5, 1.0, 2.0])))
            for i in range(n_tenants)]


# ---------------------------------------------------------------------------
# Scheduler properties (synthetic items, no solver)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 8))
def test_conservation_and_fifo(seed, n_tenants, cap):
    """Random mixes: every batch's size equals the sum of per-tenant slot
    grants, nothing is lost, and each tenant drains in FIFO order."""
    rng = np.random.default_rng(seed)
    specs = _random_specs(rng, n_tenants)
    sched = TenantScheduler(specs, budget_s=1.0, reserve_q_s=0.1)
    n_items = int(rng.integers(1, 30))
    enq = {s.name: [] for s in specs}
    t = 0.0
    for k in range(n_items):
        t += float(rng.exponential(0.05))
        name = specs[int(rng.integers(0, n_tenants))].name
        sched.enqueue(name, ("item", name, k), t)
        enq[name].append(("item", name, k))
    deq = {s.name: [] for s in specs}
    now = t
    n_flushes = 0
    while sched.total_waiting():
        n_flushes += 1
        assert n_flushes < 10 * n_items + 10, "scheduler failed to drain"
        before = {s.name: s.slots_granted for s in sched.states()}
        picked = sched.compose(now, cap)
        assert 0 < len(picked) <= cap
        grants = {s.name: s.slots_granted - before[s.name]
                  for s in sched.states()}
        assert sum(grants.values()) == len(picked)       # conservation
        for name, item, _ in picked:
            deq[name].append(item)
        now += 0.01
    assert deq == enq                                    # FIFO per tenant
    for s in sched.states():
        assert s.n_dequeued == s.n_enqueued == len(enq[s.name])


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 6))
def test_no_starvation_overdue_beats_priority(seed, cap):
    """A low-priority head whose deadline has passed is composed into the
    very next batch, no matter how much higher-priority work floods in."""
    rng = np.random.default_rng(seed)
    low = TenantSpec(name="low", priority=0,
                     share=float(rng.choice([0.5, 1.0])),
                     solve_budget_s=1.0)
    high = TenantSpec(name="high", priority=int(rng.integers(1, 4)),
                      share=3.0, solve_budget_s=10.0)
    sched = TenantScheduler([low, high], reserve_q_s=0.0)
    sched.enqueue("low", "starved", 0.0)
    for k in range(50):
        sched.enqueue("high", f"h{k}", 0.0)
    # Before low's deadline, priority preempts: batches are pure high.
    picked = sched.compose(0.5, cap)
    assert all(name == "high" for name, _, _ in picked)
    # At/after the deadline the low head is promoted ahead of every tier.
    picked = sched.compose(1.0, cap)
    assert picked[0] == ("low", "starved", False)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 4))
def test_drr_shares_track_configured_ratio(seed, share_a, share_b):
    """Two saturated same-tier tenants split batch slots ~ share_a:share_b
    (no overdue promotion in play: budgets far in the future)."""
    del seed
    a = TenantSpec(name="a", share=float(share_a), solve_budget_s=1e9)
    b = TenantSpec(name="b", share=float(share_b), solve_budget_s=1e9)
    sched = TenantScheduler([a, b], reserve_q_s=0.0)
    n = 50 * (share_a + share_b)
    for k in range(n):
        sched.enqueue("a", k, 0.0)
        sched.enqueue("b", k, 0.0)
    grants = []
    while len(grants) < n:
        grants.extend(name for name, _, _ in sched.compose(0.0, 8))
    got_a = grants[:n].count("a")
    want_a = n * share_a / (share_a + share_b)
    # DRR quantization error is bounded by one quantum per pass.
    assert abs(got_a - want_a) <= 8 + share_a + share_b


def test_tiny_share_composes_in_bounded_passes():
    """A valid-but-minuscule share must not stall composition: credits are
    normalized per pass by the tier's largest share, so each slot costs
    O(1) passes even at share=1e-9 (regression: the unnormalized loop
    needed ~1/share passes)."""
    sched = TenantScheduler([TenantSpec(name="tiny", share=1e-9,
                                        solve_budget_s=1e9)],
                            reserve_q_s=0.0)
    for k in range(4):
        sched.enqueue("tiny", k, 0.0)
    assert [i for _, i, _ in sched.compose(0.0, 4)] == [0, 1, 2, 3]
    # Ratios still respected when a tiny share competes with a normal one.
    sched2 = TenantScheduler([TenantSpec(name="tiny", share=1e-9,
                                         solve_budget_s=1e9),
                              TenantSpec(name="big", share=1.0,
                                         solve_budget_s=1e9)],
                             reserve_q_s=0.0)
    for k in range(20):
        sched2.enqueue("tiny", k, 0.0)
        sched2.enqueue("big", k, 0.0)
    grants = [n for n, _, _ in sched2.compose(0.0, 8)]
    assert grants.count("big") >= 7       # tiny earns ≪ one slot per pass


def test_priority_tier_preempts_composition():
    sched = TenantScheduler([TenantSpec(name="hi", priority=2,
                                        solve_budget_s=1e9),
                             TenantSpec(name="lo", priority=0,
                                        solve_budget_s=1e9)],
                            reserve_q_s=0.0)
    for k in range(6):
        sched.enqueue("hi", k, 0.0)
        sched.enqueue("lo", k, 0.0)
    picked = sched.compose(0.0, 4)
    assert [name for name, _, _ in picked] == ["hi"] * 4
    # Once the high tier drains, the low tier gets the whole batch.
    sched.compose(0.0, 2)
    picked = sched.compose(0.0, 4)
    assert [name for name, _, _ in picked] == ["lo"] * 4


def test_unknown_tenant_auto_registered_with_defaults():
    sched = TenantScheduler([], budget_s=2.0, reserve_q_s=0.125)
    sched.enqueue("walk-in", "x", 1.0)
    st_ = sched.state("walk-in")
    assert st_.budget_s == 2.0 and st_.reserve_q_s == 0.125
    assert st_.weights is None and st_.priority == 0
    assert st_.slo == "best_effort"
    assert sched.compose(100.0, 4) == [("walk-in", "x", False)]


def test_duplicate_tenant_specs_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        TenantScheduler([TenantSpec(name="a"), TenantSpec(name="a")])


# ---------------------------------------------------------------------------
# Overload triage: SLO classes, shed/degrade decisions (PR-5 tentpole)
# ---------------------------------------------------------------------------

def test_shed_unmeetable_pops_strict_only():
    """Only strict-SLO tenants shed; degrade/best_effort heads stay queued
    (degrade is handled at compose time, best_effort keeps waiting)."""
    sched = TenantScheduler(
        [TenantSpec(name="s", slo="strict", solve_budget_s=0.1),
         TenantSpec(name="d", slo="degrade", solve_budget_s=0.1),
         TenantSpec(name="b", slo="best_effort", solve_budget_s=0.1)],
        reserve_q_s=0.05)
    for name in ("s", "d", "b"):
        sched.enqueue(name, f"{name}0", 0.0)
        sched.enqueue(name, f"{name}1", 0.0)
    shed = sched.shed_unmeetable(10.0, cap=8)       # way past every budget
    assert shed == [("s", "s0"), ("s", "s1")]
    st = sched.state("s")
    assert st.n_shed == 2 and st.waiting == 0
    assert st.slots_granted == 0                    # shed ≠ batch slots
    # The others were untouched and compose with the right degrade flags.
    picked = sched.compose(10.0, cap=8)
    assert sorted((n, i, g) for n, i, g in picked) == [
        ("b", "b0", False), ("b", "b1", False),
        ("d", "d0", True), ("d", "d1", True)]
    assert sched.state("d").n_degraded == 2
    assert sched.state("b").n_degraded == 0


def test_shed_respects_expected_batch_scaling():
    """Unmeetable is `arrival + budget − reserve·E[n] < now` — with a big
    backlog the expected solve is longer, so heads shed earlier; and the
    expected size is re-derived as the pool drains, so shedding stops as
    soon as the remaining batch became small enough to meet the budget."""
    sched = TenantScheduler([TenantSpec(name="s", slo="strict",
                                        solve_budget_s=0.8)],
                            reserve_q_s=0.25)
    for k in range(4):
        sched.enqueue("s", k, 0.0)
    # E[4]: deadline = 0.8 − 4·0.25 = −0.2 < 0.05 → shed the head.  After
    # one shed E[3]: deadline = 0.8 − 0.75 = 0.05, NOT strictly < now →
    # the rest are meetable and must survive.
    shed = sched.shed_unmeetable(0.05, cap=8)
    assert [i for _, i in shed] == [0]
    assert sched.state("s").waiting == 3


def test_degrade_flag_sized_to_the_batch_being_composed():
    """The degrade check's E[n] counts already-picked slots plus the
    remaining pool: every member of one compose shares one flush window,
    so if the 4-item batch blows the budget, *all four* are admitted
    degraded — a remaining-pool-only E[n] would mark just the first and
    burn full solves into an already-blown budget."""
    sched = TenantScheduler([TenantSpec(name="d", slo="degrade",
                                        solve_budget_s=0.8)],
                            reserve_q_s=0.25)
    for k in range(4):
        sched.enqueue("d", k, 0.0)
    # E[n]=4 throughout: deadline = 0.8 − 4·0.25 = −0.2 < 0.05 for every
    # member of the batch.
    picked = sched.compose(0.05, cap=8)
    assert [i for _, i, _ in picked] == [0, 1, 2, 3]    # FIFO preserved
    assert [g for _, _, g in picked] == [True, True, True, True]
    assert sched.state("d").n_degraded == 4
    # A later, genuinely smaller batch is meetable again: nothing sticky.
    sched.enqueue("d", 4, 10.0)
    assert sched.compose(10.0, cap=8) == [("d", 4, False)]


def test_meetable_degrade_tenant_not_degraded():
    sched = TenantScheduler([TenantSpec(name="d", slo="degrade",
                                        solve_budget_s=10.0)],
                            reserve_q_s=0.1)
    sched.enqueue("d", "x", 0.0)
    assert sched.compose(0.0, cap=4) == [("d", "x", False)]
    assert sched.state("d").n_degraded == 0


def test_slo_class_validated():
    with pytest.raises(ValueError, match="SLO class"):
        TenantSpec(name="x", slo="bogus")


# ---------------------------------------------------------------------------
# DRR credit double-dip (PR-5 bugfix): overdue pops charge the deficit
# ---------------------------------------------------------------------------

def test_overdue_pop_consumes_banked_credit():
    """A tenant served via overdue promotion must pay for the slot out of
    its banked DRR credit (floored at the standard empty-queue reset of
    0), not keep it for a double-dip on the next normal pass."""
    a = TenantSpec(name="a", solve_budget_s=1.0)
    b = TenantSpec(name="b", solve_budget_s=1e9)
    sched = TenantScheduler([a, b], reserve_q_s=0.0)
    for k in range(4):
        sched.enqueue("a", f"a{k}", 0.0)
        sched.enqueue("b", f"b{k}", 100.0)
    sched.state("a").deficit = 1.0          # banked from earlier passes
    # a's head is overdue at t=2: promoted — and the banked credit is
    # spent by the promotion.
    picked = sched.compose(2.0, cap=2)
    assert picked[0].tenant == "a"
    assert sched.state("a").deficit == 0.0
    # Floor at the standard reset: promotion never drives credit negative.
    sched.state("a").deficit = 0.25
    picked = sched.compose(2.0, cap=1)
    assert picked[0].tenant == "a" and sched.state("a").deficit == 0.0


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 6))
def test_bursty_overdue_traffic_properties(seed, n_tenants, cap):
    """Fairness properties under bursty-*overdue* traffic: random mixes
    where a fraction of every tenant's arrivals are long past their budget
    (so overdue promotion, the deficit charge, and the drain-aware E[n]
    all exercise every compose).  Invariants: slot conservation, per-tenant
    FIFO, DRR credit never negative (promotion charges floor at the
    standard reset), and an emptied queue always resets its credit."""
    rng = np.random.default_rng(seed)
    specs = _random_specs(rng, n_tenants)
    sched = TenantScheduler(specs, budget_s=0.5, reserve_q_s=0.1)
    now = 100.0
    enq = {s.name: [] for s in specs}
    n_items = int(rng.integers(2, 40))
    for k in range(n_items):
        name = specs[int(rng.integers(0, n_tenants))].name
        # ~half the arrivals are stale: overdue (promoted) at compose time.
        arrival = 0.0 if rng.random() < 0.5 else now + 1.0
        sched.enqueue(name, ("item", name, k), arrival)
        enq[name].append(("item", name, k))
    deq = {s.name: [] for s in specs}
    n_flushes = 0
    while sched.total_waiting():
        n_flushes += 1
        assert n_flushes < 10 * n_items + 10, "scheduler failed to drain"
        before = {s.name: s.slots_granted for s in sched.states()}
        picked = sched.compose(now, cap)
        assert 0 < len(picked) <= cap
        grants = {s.name: s.slots_granted - before[s.name]
                  for s in sched.states()}
        assert sum(grants.values()) == len(picked)       # conservation
        for s in sched.states():
            assert s.deficit >= 0.0                      # charge floored
            if not s.queue:
                assert s.deficit == 0.0                  # standard reset
        for name, item, _ in picked:
            deq[name].append(item)
    assert deq == enq                                    # FIFO per tenant


# ---------------------------------------------------------------------------
# Per-query reserve EWMA (regression: batch size used to be ignored)
# ---------------------------------------------------------------------------

def test_reserve_normalized_per_query():
    """One large batch must not inflate the reserve applied to a later
    single-query flush: the EWMA tracks dt/n, not raw batch dt."""
    sched = TenantScheduler([], budget_s=1.0, reserve_q_s=0.25,
                            reserve_ewma=0.3)
    sched.note_solve(8.0, 8, ["a"])            # 1.0 s per query
    st_ = sched.state("a")
    assert st_.reserve_q_s == pytest.approx(0.7 * 0.25 + 0.3 * 1.0)
    # The buggy whole-batch EWMA would have been 0.7*0.25 + 0.3*8.0 = 2.575,
    # pushing a single waiting query's deadline before its own arrival.
    sched.enqueue("a", "x", arrival_s=10.0)
    dl = 10.0 + 1.0 - st_.reserve_q_s
    assert dl > 10.0                            # still after arrival
    assert not sched.unmeetable(st_, dl, cap=8)
    assert sched.unmeetable(st_, dl + 1e-9, cap=8)
    # With more waiting, the deadline scales the per-query reserve back up
    # by the expected batch size.
    for k in range(3):
        sched.enqueue("a", k, arrival_s=10.0)
    dl = 10.0 + 1.0 - 4 * st_.reserve_q_s
    assert not sched.unmeetable(st_, dl, cap=8)
    assert sched.unmeetable(st_, dl + 1e-9, cap=8)


def test_reserve_tracks_full_charged_window():
    """Regression (PR-5): the reserve EWMA must be fed the *full* flush
    window the simulated clock charges — the batched solve plus each
    query's initial AQE planning step inside ``session.admit()`` — not
    just the ``tune_batch`` slice.  Replaying the EWMA over the recorded
    per-flush clock charges must land exactly on the live reserve, which
    is therefore ≥ the charged per-query clock cost folded at the EWMA
    rate (the old under-measurement made it strictly smaller)."""
    cfg = ServerConfig(max_batch=4, solve_reserve_s=0.0)
    srv = OptimizerServer(config=cfg, weights=WEIGHTS, cfg=CFG)
    stream = serving_stream("tpch", 10, seed=12,
                            arrivals=ArrivalModel(kind="poisson",
                                                  rate_qps=40.0))
    srv.serve(stream)
    windows = srv.last_run.flush_windows
    assert len(windows) >= 2
    a = srv.scheduler.reserve_ewma
    replay = cfg.solve_reserve_s
    for dt, n in windows:
        assert dt > 0 and n > 0
        replay = (1 - a) * replay + a * dt / n
    got = srv.scheduler.state("default").reserve_q_s
    assert got == pytest.approx(replay, rel=1e-9)
    assert srv.scheduler.default_reserve_q_s == pytest.approx(replay,
                                                              rel=1e-9)
    # Convexity: an EWMA of per-query charges (seeded at 0) dominates the
    # smallest charged per-query cost scaled by the folded-in weight — the
    # "reserve ≥ charged per-query clock cost" convergence guarantee.
    min_q = min(dt / n for dt, n in windows)
    assert got >= (1 - (1 - a) ** len(windows)) * min_q


def test_reserve_scales_only_own_tenant():
    sched = TenantScheduler([], budget_s=1.0, reserve_q_s=0.2)
    sched.note_solve(4.0, 4, ["a"])
    assert sched.state("a").reserve_q_s > 0.2
    # Fresh tenants seed from the updated global default, not the old seed.
    assert sched.state("b").reserve_q_s == sched.default_reserve_q_s


# ---------------------------------------------------------------------------
# Server level: single-tenant ≡ PR-3, mixed mixes all served + conserved
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def solo_stream():
    return serving_stream("tpch", 10, seed=4,
                          arrivals=ArrivalModel(kind="poisson",
                                                rate_qps=40.0))


def test_single_tenant_reproduces_anonymous_path(solo_stream):
    """The same stream served anonymously and under a named single tenant
    (same weights) yields bit-identical outputs and identical admission
    accounting — the multi-tenant machinery is a no-op at n_tenants=1."""
    anon = OptimizerServer(config=ServerConfig(max_batch=4), weights=WEIGHTS,
                           cfg=CFG)
    a = anon.serve(solo_stream)
    named_reqs = [dataclasses.replace(r, tenant="alice")
                  for r in solo_stream]
    named = OptimizerServer(
        config=ServerConfig(max_batch=4), weights=WEIGHTS, cfg=CFG,
        tenants=[TenantSpec(name="alice", weights=WEIGHTS)])
    b = named.serve(named_reqs)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.result.theta_p_eff,
                                      y.result.theta_p_eff)
        np.testing.assert_array_equal(x.result.theta_s_eff,
                                      y.result.theta_s_eff)
        np.testing.assert_array_equal(x.result.final_join,
                                      y.result.final_join)
        np.testing.assert_array_equal(x.result.sim.cost, y.result.sim.cost)
    # (Batch *composition* depends on measured wall time and may differ
    # run to run; the invariant is that outputs and accounting do not.)
    assert sum(anon.last_run.tenant_slots.values()) == len(solo_stream)
    assert named.last_run.tenant_slots == {"alice": len(solo_stream)}


def test_mixed_tenant_stream_all_served_and_conserved():
    specs = [TenantSpec(name="a", weights=(0.9, 0.1), share=2.0,
                        arrivals=ArrivalModel(rate_qps=30.0)),
             TenantSpec(name="b", weights=(0.5, 0.5), priority=1,
                        arrivals=ArrivalModel(rate_qps=30.0)),
             TenantSpec(name="c", arrivals=ArrivalModel(rate_qps=15.0),
                        solve_budget_s=0.5)]
    reqs = multi_tenant_stream("tpch", specs, [5, 4, 3], seed=6)
    assert len(reqs) == 12
    assert [r.rid for r in reqs] == list(range(12))
    srv = OptimizerServer(config=ServerConfig(max_batch=4), weights=WEIGHTS,
                          cfg=CFG, tenants=specs)
    served = srv.serve(reqs)
    assert all(s.result is not None for s in served)
    assert all(math.isfinite(s.finished_s) for s in served)
    # Slot accounting conserves across the whole run.
    assert sum(srv.last_run.tenant_slots.values()) == len(reqs)
    assert srv.last_run.tenant_slots == {"a": 5, "b": 4, "c": 3}
    rep = srv.latency_report(served)
    assert set(rep["tenants"]) == {"a", "b", "c"}
    assert 0.0 < rep["fairness_jain"] <= 1.0
    # Tenant "c" (no weights configured) fell back to the server default.
    assert srv.tenant_weights("c") == WEIGHTS


def test_serve_refuses_nonempty_admission_queue(solo_stream):
    srv = OptimizerServer(config=ServerConfig(max_batch=4), weights=WEIGHTS,
                          cfg=CFG)
    srv.scheduler.enqueue("default", "stray", 0.0)
    with pytest.raises(RuntimeError, match="admission queue"):
        srv.serve(solo_stream)


def test_multi_tenant_stream_validation():
    with pytest.raises(ValueError, match="duplicate tenant"):
        multi_tenant_stream("tpch", [TenantSpec(name="x"),
                                     TenantSpec(name="x")], 2)
    with pytest.raises(ValueError, match="counts"):
        multi_tenant_stream("tpch", [TenantSpec(name="x")], [1, 2])
    with pytest.raises(ValueError, match="share"):
        TenantSpec(name="x", share=0.0)
    with pytest.raises(ValueError, match="non-empty"):
        TenantSpec(name="")


def test_multi_tenant_stream_reproducible_and_independent():
    specs = [TenantSpec(name="a", arrivals=ArrivalModel(rate_qps=10.0)),
             TenantSpec(name="b", arrivals=ArrivalModel(rate_qps=10.0))]
    r1 = multi_tenant_stream("tpch", specs, 6, seed=9)
    r2 = multi_tenant_stream("tpch", specs, 6, seed=9)
    assert [(r.tenant, r.arrival_s, r.query.qid) for r in r1] == \
           [(r.tenant, r.arrival_s, r.query.qid) for r in r2]
    times = [r.arrival_s for r in r1]
    assert times == sorted(times)
    # Tenants draw distinct populations/timings (independent seed streams).
    a = [r.query.qid for r in r1 if r.tenant == "a"]
    b = [r.query.qid for r in r1 if r.tenant == "b"]
    assert a != b
    assert all(isinstance(r, StreamRequest) for r in r1)


# ---------------------------------------------------------------------------
# Token-bucket properties (PR-8: per-tenant rate limiting at the door)
# ---------------------------------------------------------------------------

from repro.serve import ElasticController, ElasticPolicy, TokenBucket  # noqa: E402


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 8), st.floats(min_value=0.5, max_value=20.0))
def test_bucket_burst_is_the_instantaneous_cap(burst, rate):
    """A fresh bucket at a single instant admits exactly ``burst`` takes
    — never more, regardless of rate."""
    b = TokenBucket(rate_qps=rate, burst=float(burst))
    admitted = sum(b.take(0.0) for _ in range(burst + 5))
    assert admitted == burst


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(min_value=0.5, max_value=8.0),
       st.integers(1, 6))
def test_bucket_conserves_tokens(seed, rate, burst):
    """Over any arrival pattern, admissions never exceed the refill
    budget: ``admitted <= burst + elapsed * rate`` at every prefix."""
    rng = np.random.default_rng(seed)
    b = TokenBucket(rate_qps=rate, burst=float(burst))
    t, admitted = 0.0, 0
    for _ in range(60):
        t += float(rng.exponential(0.3))
        admitted += b.take(t)
        assert admitted <= burst + t * rate + 1e-9
        assert 0.0 <= b.tokens <= burst


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(min_value=0.5, max_value=8.0))
def test_bucket_no_starvation_after_idle(seed, rate):
    """However drained, one full refill interval (``1/rate``) always buys
    the next take — a tenant that backs off is never locked out."""
    rng = np.random.default_rng(seed)
    b = TokenBucket(rate_qps=rate, burst=2.0)
    t = 0.0
    for _ in range(20):
        t += float(rng.exponential(0.05))
        b.take(t)          # hammer the bucket (mostly rejected)
    t += 1.0 / rate + 1e-9
    assert b.take(t)


def test_bucket_ignores_clock_regressions():
    """An out-of-order arrival must not refill (monotone-clock guard) —
    otherwise replay order could mint tokens."""
    b = TokenBucket(rate_qps=1.0, burst=1.0)
    assert b.take(10.0)
    assert not b.take(10.5)
    assert not b.take(0.0)     # regression: no refill, no admit
    assert not b.take(10.6)    # and no token appeared meanwhile
    assert b.take(11.5)        # a full second after the last refill point


def test_bucket_validation():
    with pytest.raises(ValueError, match="rate_qps"):
        TokenBucket(rate_qps=0.0, burst=1.0)
    with pytest.raises(ValueError, match="burst"):
        TokenBucket(rate_qps=1.0, burst=0.0)


def test_admit_arrival_routes_and_counts():
    sched = TenantScheduler([TenantSpec(name="rl", rate_limit_qps=1.0,
                                        rate_limit_burst=1.0),
                             TenantSpec(name="free")])
    assert sched.admit_arrival("rl", "a", 0.0)
    assert not sched.admit_arrival("rl", "b", 0.1)
    assert sched.admit_arrival("rl", "c", 1.2)
    for i in range(5):       # no bucket → always admitted
        assert sched.admit_arrival("free", i, 0.0)
    rl, free = sched.state("rl"), sched.state("free")
    assert (rl.n_enqueued, rl.n_rate_limited) == (2, 1)
    assert (free.n_enqueued, free.n_rate_limited) == (5, 0)
    picked = sched.compose(0.0, cap=8) + sched.compose(0.0, cap=8)
    # Only admitted items reach composition; per-tenant FIFO is preserved.
    assert [it for name, it, _ in picked if name == "rl"] == ["a", "c"]
    assert [it for name, it, _ in picked if name == "free"] == list(range(5))


# ---------------------------------------------------------------------------
# Elastic-controller properties (PR-8: capacity follows the forecast)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4), st.integers(4, 32),
       st.floats(min_value=0.05, max_value=2.0))
def test_elastic_monotone_in_forecast(seed, min_b, max_b, target):
    """The controller contract: a *higher* queue-delay forecast never
    lowers the batch cap, never raises headroom, and never shortens the
    degrade lead — so pressure only ever moves the knobs toward relief."""
    rng = np.random.default_rng(seed)
    pol = ElasticPolicy(min_batch=min_b, max_batch=max_b,
                        target_delay_s=target)
    base_cap, budget, reserve = 4, 1.0, 0.05
    forecasts = np.sort(rng.uniform(0.0, 5.0 * target, size=12))
    caps, heads, leads = [], [], []
    for f in forecasts:
        c = ElasticController(pol)
        c.forecast_s = float(f)
        caps.append(c.batch_cap(base_cap))
        heads.append(c.headroom_s(budget, reserve, base_cap))
        leads.append(c.degrade_lead_s(budget, reserve, base_cap))
    assert all(min_b <= c <= max_b for c in caps)
    assert all(b >= a for a, b in zip(caps, caps[1:]))
    assert all(b <= a + 1e-12 for a, b in zip(heads, heads[1:]))
    assert all(b >= a - 1e-12 for a, b in zip(leads, leads[1:]))
    assert all(0.0 <= l <= budget for l in leads)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1), st.floats(min_value=0.05, max_value=1.0))
def test_elastic_forecast_is_the_ewma_of_flush_delays(seed, alpha):
    rng = np.random.default_rng(seed)
    ctl = ElasticController(ElasticPolicy(ewma=alpha))
    ref = 0.0
    for d in rng.uniform(0.0, 2.0, size=10):
        ctl.note_flush(float(d))
        ref = (1 - alpha) * ref + alpha * float(d)
        assert ctl.forecast_s == pytest.approx(ref)
    assert ctl.n_windows == 10
    ctl.note_flush(-5.0)       # negative delay is clamped, not absorbed
    assert ctl.forecast_s >= 0.0


def test_elastic_no_pressure_means_base_cap():
    ctl = ElasticController(ElasticPolicy(min_batch=1, max_batch=32))
    assert ctl.forecast_s == 0.0
    for base in (1, 4, 32):
        assert ctl.batch_cap(base) == base
    assert ctl.degrade_lead_s(1.0, 0.05, 4) == 0.0


def test_elastic_ceiling_never_clamps_the_provisioned_base():
    """max_batch bounds the *scaling*, not the deployment: a capacity
    event raising the base cap above the elastic ceiling passes through
    unclamped (elasticity adds capacity, never subtracts it)."""
    ctl = ElasticController(ElasticPolicy(min_batch=1, max_batch=4,
                                          target_delay_s=0.1))
    assert ctl.batch_cap(8) == 8                 # base above ceiling
    ctl.forecast_s = 10.0                        # saturated pressure
    assert ctl.batch_cap(8) == 8                 # still the base, not 4
    assert ctl.batch_cap(1) == 4                 # scaling capped at 4
