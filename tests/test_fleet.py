"""Fleet serving: sharded golden determinism, routing, cache warm-start.

The fleet's acceptance invariant extends the server's: per-tenant served
outputs are bit-identical to the offline ``tune_batch`` →
``RuntimeSession.run_batch`` pipeline under ANY worker count and ANY
routing policy — sharding and work stealing change only latency, never
what is served.
"""
import dataclasses
import math
import pickle

import numpy as np
import pytest

from repro.core.moo.hmooc import HMOOCConfig
from repro.queryengine.workloads import (ArrivalModel, StreamRequest,
                                         TenantSpec, make_query,
                                         multi_tenant_stream, serving_stream)
from repro.serve import (CacheStore, FleetRouter, HashRing, OptimizerFleet,
                         RuntimeSession, ServerConfig, ServiceTimeModel,
                         TuningService, route_key)

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)
WEIGHTS = (0.9, 0.1)
N_STREAM = 10
CLOCK = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)), round_s=0.005,
                         cheap_s=0.001, worker_scale=((1, 1.0), (4, 1.25)))


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
    return RuntimeSession(weights=WEIGHTS).run_batch(queries, cts)


def _fleet(n_workers, **kw):
    kw.setdefault("config", ServerConfig(max_batch=4, clock=CLOCK))
    return OptimizerFleet(n_workers=n_workers, weights=WEIGHTS, cfg=CFG, **kw)


def _assert_same_outputs(served, offline_results):
    for s, ref in zip(served, offline_results):
        got = s.result
        np.testing.assert_array_equal(got.theta_p_eff, ref.theta_p_eff)
        np.testing.assert_array_equal(got.theta_s_eff, ref.theta_s_eff)
        np.testing.assert_array_equal(got.final_join, ref.final_join)
        np.testing.assert_array_equal(got.sim.ana_latency, ref.sim.ana_latency)
        np.testing.assert_array_equal(got.sim.cost, ref.sim.cost)


# ---------------------------------------------------------------------------
# Golden determinism under sharding (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["affinity", "random"])
@pytest.mark.parametrize("n_workers", [1, 2, 4])
def test_fleet_outputs_bit_identical_to_offline(timed_stream, offline,
                                                n_workers, policy):
    fleet = _fleet(n_workers, policy=policy)
    served = fleet.serve(timed_stream)
    _assert_same_outputs(served, offline)
    st = fleet.last_run
    assert st.n_finished == len(timed_stream)
    assert sum(st.worker_counts) == len(timed_stream)
    assert all(s.worker is not None and 0 <= s.worker < n_workers
               for s in served)
    assert st.qps > 0.0 and math.isfinite(st.makespan_s)


def test_fleet_work_stealing_preserves_outputs(timed_stream, offline):
    """Force heavy stealing (everything arrives at once, zero tolerated
    delay): requests leave their affinity owners, outputs still
    bit-match."""
    reqs = [dataclasses.replace(r, arrival_s=0.0) for r in timed_stream]
    fleet = _fleet(4, policy="affinity", steal_delay_s=0.0)
    served = fleet.serve(reqs)
    by_rid = {s.rid: s for s in served}
    _assert_same_outputs([by_rid[r.rid] for r in timed_stream], offline)
    st = fleet.last_run
    assert st.n_stolen > 0
    assert sum(1 for c in st.worker_counts if c) > 1   # genuinely spread


def test_fleet_replay_is_deterministic(timed_stream):
    """Two identical fleets over the same stream: identical assignments,
    statuses, timelines, and bits (serve() is a pure function of stream +
    config under a ServiceTimeModel)."""
    def run():
        return _fleet(2, policy="affinity", steal_delay_s=0.05) \
            .serve(timed_stream)

    for x, y in zip(run(), run()):
        assert x.worker == y.worker and x.status == y.status
        assert x.finished_s == y.finished_s
        np.testing.assert_array_equal(x.result.theta_p_eff,
                                      y.result.theta_p_eff)


def test_fleet_multi_tenant_survivor_parity():
    """Each tenant's output through a 2-worker fleet is bit-identical to
    the offline pipeline under that tenant's own weights."""
    specs = [TenantSpec(name="lat", weights=(0.9, 0.1),
                        arrivals=ArrivalModel(kind="poisson", rate_qps=30.0)),
             TenantSpec(name="cost", weights=(0.1, 0.9),
                        arrivals=ArrivalModel(kind="uniform", rate_qps=10.0))]
    reqs = multi_tenant_stream("tpch", specs, 3, seed=8)
    fleet = OptimizerFleet(n_workers=2,
                           config=ServerConfig(max_batch=4, clock=CLOCK),
                           weights=WEIGHTS, cfg=CFG, tenants=specs)
    served = fleet.serve(reqs)
    for spec in specs:
        sub = [s for s in served if s.tenant == spec.name]
        assert len(sub) == 3
        queries = [s.request.query for s in sub]
        cts = TuningService(cfg=CFG).tune_batch(queries, spec.weights)
        ref = RuntimeSession(weights=spec.weights).run_batch(queries, cts)
        _assert_same_outputs(sub, ref)


# ---------------------------------------------------------------------------
# Cache store: snapshot/warm-start round trip (satellite acceptance)
# ---------------------------------------------------------------------------

def test_fleet_warm_start_round_trip(tmp_path, timed_stream, offline):
    """Cold worker vs a worker restored from its published snapshots:
    bit-identical responses and the warm-replay hit taxonomy (everything
    from the response cache, zero new solver work) — through a file, so
    the warmth genuinely survives the process boundary."""
    store = CacheStore()
    cold = _fleet(1, cache_store=store)
    first = cold.serve(timed_stream)                   # publishes snapshots
    assert set(store.kinds()) == {"eset", "response", "pools"}
    second = cold.serve(timed_stream)                  # warm-replay reference

    path = tmp_path / "caches.pkl"
    store.save(path)
    loaded = CacheStore.load(path)
    assert loaded.kinds() == store.kinds()
    assert all(loaded.fetch(k) == store.fetch(k) for k in store.kinds())

    warm = _fleet(1, cache_store=loaded, publish_on_serve=False)
    srv = warm.workers[0]
    assert len(srv.tuning._results) > 0                # warm before serving
    third = warm.serve(timed_stream)
    _assert_same_outputs(third, offline)
    for a, b, c in zip(first, second, third):
        np.testing.assert_array_equal(a.result.theta_p_eff,
                                      c.result.theta_p_eff)
        np.testing.assert_array_equal(b.result.theta_p_eff,
                                      c.result.theta_p_eff)
    # Identical hit taxonomy to the cold worker's own warm replay: all
    # responses deduped, no effective-set misses, no fresh pool draws.
    assert srv.tuning._results.misses == 0
    assert srv.tuning._results.hits == len(timed_stream)
    assert srv.tuning.cache.stats()["misses"] == 0
    assert srv.session.pool_cache.misses == 0
    rep = warm.cache_report()
    assert rep["response"]["hit_rate"] == pytest.approx(1.0)


def test_fleet_publish_merges_across_workers(timed_stream):
    """A sharded fleet's published snapshot is the union of its workers'
    eligible entries; a 1-worker fleet warm-started from it replays the
    whole stream without solving."""
    store = CacheStore()
    sharded = _fleet(2, policy="affinity", cache_store=store)
    sharded.serve(timed_stream)
    warm = _fleet(1, cache_store=store, publish_on_serve=False)
    warm.serve(timed_stream)
    assert warm.workers[0].tuning._results.misses == 0
    assert warm.workers[0].session.pool_cache.misses == 0


def test_warm_start_never_changes_outputs(timed_stream, offline):
    """Cache warmth moves hit rates and timing only: a warm-started fleet
    and a cold fleet serve the same bits (restore entries are exact
    artifacts for their keys)."""
    store = CacheStore()
    _fleet(2, policy="random", cache_store=store).serve(timed_stream)
    warm = _fleet(2, policy="affinity", cache_store=store,
                  publish_on_serve=False)
    _assert_same_outputs(warm.serve(timed_stream), offline)


def test_cache_store_validation(tmp_path):
    store = CacheStore()
    with pytest.raises(ValueError, match="unknown cache kind"):
        store.publish("bogus", b"x")
    with pytest.raises(TypeError, match="bytes"):
        store.publish("eset", "not-bytes")
    assert store.fetch("eset") is None and store.kinds() == ()
    p = tmp_path / "foreign.pkl"
    with open(p, "wb") as f:
        pickle.dump({"format": "something-else"}, f)
    with pytest.raises(ValueError, match="not a cache-store"):
        CacheStore.load(p)
    p2 = tmp_path / "skewed.pkl"
    with open(p2, "wb") as f:
        pickle.dump({"format": "repro-cache-store", "version": 99,
                     "blobs": {}}, f)
    with pytest.raises(ValueError, match="version"):
        CacheStore.load(p2)


# ---------------------------------------------------------------------------
# Router / ring mechanics
# ---------------------------------------------------------------------------

def test_hash_ring_deterministic_and_consistent():
    keys = [(b, t) for b in ("tpch", "tpcds") for t in range(100)]
    owners4 = [HashRing(4).worker_for(k) for k in keys]
    assert owners4 == [HashRing(4).worker_for(k) for k in keys]
    assert set(owners4) == {0, 1, 2, 3}                # no dead workers
    # Consistency: growing 4 -> 5 moves only keys captured by the new
    # worker's points — nothing reshuffles between old workers.
    owners5 = [HashRing(5).worker_for(k) for k in keys]
    moved = [i for i, (a, b) in enumerate(zip(owners4, owners5)) if a != b]
    assert moved and len(moved) < len(keys) // 2
    assert all(owners5[i] == 4 for i in moved)
    with pytest.raises(ValueError, match="n_workers"):
        HashRing(0)
    with pytest.raises(ValueError, match="replicas"):
        HashRing(2, replicas=0)


def test_router_policies():
    reqs = [StreamRequest(rid=i, query=make_query("tpch", i % 3, variant=i),
                          arrival_s=0.01 * i) for i in range(9)]
    with pytest.raises(ValueError, match="routing policy"):
        FleetRouter(2, policy="bogus")
    with pytest.raises(ValueError, match="steal_delay_s"):
        FleetRouter(2, steal_delay_s=-1.0)
    assert FleetRouter(3, policy="single").assign(reqs) == [0] * 9
    rnd = FleetRouter(3, policy="random", seed=5).assign(reqs)
    assert rnd == FleetRouter(3, policy="random", seed=5).assign(reqs)
    assert rnd != FleetRouter(3, policy="random", seed=6).assign(reqs)
    # Strict affinity is exactly the ring over the template dims.
    aff = FleetRouter(3, policy="affinity").assign(reqs)
    ring = HashRing(3)
    assert aff == [ring.worker_for(route_key(r.query)) for r in reqs]
    # ... so every variant of one template shares a worker.
    for t in range(3):
        assert len({w for r, w in zip(reqs, aff)
                    if r.query.template == t}) == 1


def test_affinity_routing_keeps_caches_warmer_than_random():
    """Two workers on the modelled clock, one tenant per SLO class: routing
    by template affinity reuses Algorithm-1 banks more often than random
    routing, and hits the response cache at least as often."""
    specs = [TenantSpec(name=slo, slo=slo, weights=w,
                        arrivals=ArrivalModel(kind="poisson",
                                              rate_qps=40.0 / 3))
             for slo, w in (("strict", (0.9, 0.1)), ("degrade", (0.7, 0.3)),
                            ("best_effort", (0.5, 0.5)))]
    reqs = multi_tenant_stream("tpch", specs, 6, seed=0)
    rates = {}
    for policy in ("affinity", "random"):
        fleet = _fleet(2, policy=policy, tenants=specs, seed=0,
                       config=ServerConfig(max_batch=4, solve_budget_s=1.0,
                                           clock=CLOCK))
        fleet.serve(reqs)
        cr = fleet.cache_report()
        rates[policy] = (cr["effective_set"]["warm_rate"],
                         cr["response"]["hit_rate"])
    assert rates["affinity"][0] > rates["random"][0]
    assert rates["affinity"][1] >= rates["random"][1]


def test_router_assignment_is_input_order_invariant():
    """Routing happens in (arrival_s, rid) order regardless of how the
    request list is permuted: per-rid assignments never move."""
    reqs = [StreamRequest(rid=i, query=make_query("tpch", i % 4, variant=i),
                          arrival_s=0.02 * (i % 5)) for i in range(12)]
    ref = dict(zip((r.rid for r in reqs),
                   FleetRouter(3, steal_delay_s=0.01).assign(reqs)))
    perm = list(reversed(reqs))
    got = dict(zip((r.rid for r in perm),
                   FleetRouter(3, steal_delay_s=0.01).assign(perm)))
    assert got == ref


def test_router_work_stealing_spreads_backlog():
    """Simultaneous arrivals of one hot template: strict affinity piles
    them on the owner; with a delay bound the backlog forecast sends the
    overflow to idle workers (ties to the lowest index)."""
    reqs = [StreamRequest(rid=i, query=make_query("tpch", 2, variant=i),
                          arrival_s=0.0) for i in range(6)]
    strict = FleetRouter(3, steal_delay_s=None, est_full_s=0.25)
    assert len(set(strict.assign(reqs))) == 1 and strict.n_stolen == 0
    steal = FleetRouter(3, steal_delay_s=0.1, est_full_s=0.25)
    out = steal.assign(reqs)
    assert steal.n_stolen > 0 and len(set(out)) == 3
    assert sum(steal.worker_counts) == len(reqs)
    # Spaced-out arrivals never exceed the delay bound: no stealing.
    spaced = [dataclasses.replace(r, arrival_s=0.3 * i)
              for i, r in enumerate(reqs)]
    relaxed = FleetRouter(3, steal_delay_s=0.1, est_full_s=0.25)
    assert len(set(relaxed.assign(spaced))) == 1 and relaxed.n_stolen == 0


# ---------------------------------------------------------------------------
# Construction / reporting plumbing
# ---------------------------------------------------------------------------

def test_fleet_construction_validation():
    with pytest.raises(ValueError, match="n_workers"):
        OptimizerFleet(n_workers=0, cfg=CFG)
    with pytest.raises(ValueError, match="routing policy"):
        OptimizerFleet(n_workers=2, cfg=CFG, policy="bogus")
    fleet = _fleet(4)
    # The clock is re-priced for co-located contention at fleet width.
    assert fleet.config.clock.n_workers == 4
    assert all(w.config.clock.n_workers == 4 for w in fleet.workers)
    with pytest.raises(RuntimeError, match="no cache store"):
        fleet.publish()


def test_fleet_reports(timed_stream):
    fleet = _fleet(2, policy="affinity")
    served = fleet.serve(timed_stream)
    rep = fleet.latency_report(served)
    assert rep["n_queries"] == len(timed_stream)
    assert rep["n_workers"] == 2 and rep["policy"] == "affinity"
    assert rep["worker_counts"] == fleet.last_run.worker_counts
    assert rep["qps"] == fleet.last_run.qps
    assert rep["n_micro_batches"] >= 1
    cr = fleet.cache_report()
    assert set(cr) == {"effective_set", "response", "pools"}
    assert 0.0 <= cr["effective_set"]["warm_rate"] <= 1.0
    assert 0.0 <= cr["response"]["hit_rate"] <= 1.0
