"""Compile-time/runtime tuning pipeline + aggregation + cluster autotuner."""
import numpy as np
import pytest

from repro.core.moo.hmooc import HMOOCConfig
from repro.core.moo.pareto import pareto_mask_np
from repro.core.moo.baselines import solve_evo, solve_pf, solve_so_fw, \
    solve_ws
from repro.core.tuning.aggregation import aggregate_submission_theta
from repro.core.tuning.compile_time import compile_time_optimize
from repro.core.tuning.objectives import StageObjectives
from repro.core.tuning.runtime import make_runtime_optimizers
from repro.queryengine.aqe import run_with_aqe
from repro.queryengine.simulator import default_theta
from repro.queryengine.workloads import make_benchmark


@pytest.fixture(scope="module")
def q9():
    return make_benchmark("tpch")[8]


def test_compile_time_beats_default(q9):
    tc, tp, ts = default_theta(1)
    r_def = run_with_aqe(q9, tc[0], tp[0], ts[0])
    ct = compile_time_optimize(q9, weights=(0.9, 0.1),
                               cfg=HMOOCConfig(seed=0))
    r_opt = run_with_aqe(q9, ct.theta_c, ct.theta_p0, ct.theta_s0)
    assert r_opt.sim.actual_latency[0] < r_def.sim.actual_latency[0]
    assert ct.solve_time < 2.0      # paper's cloud constraint: 1–2 s


def test_served_plans_beat_spark_defaults():
    """Plan quality (paper Table 4): over the 22 TPC-H templates, the θ the
    service picks on the oracle objective at ``HMOOCConfig()`` runs faster
    under AQE on the simulator, on average, than the Spark defaults."""
    from repro.serve import TuningService
    queries = make_benchmark("tpch")
    cts = TuningService(cfg=HMOOCConfig()).tune_batch(queries, (0.9, 0.1))
    tc, tp, ts = default_theta(1)
    default = [run_with_aqe(q, tc[0], tp[0], ts[0]).sim.actual_latency[0]
               for q in queries]
    tuned = [run_with_aqe(q, ct.theta_c, ct.theta_p0, ct.theta_s0
                          ).sim.actual_latency[0]
             for q, ct in zip(queries, cts)]
    assert np.mean(tuned) < np.mean(default)


def test_runtime_opt_no_worse(q9):
    ct = compile_time_optimize(q9, weights=(0.9, 0.1),
                               cfg=HMOOCConfig(seed=0))
    r_ct = run_with_aqe(q9, ct.theta_c, ct.theta_p0, ct.theta_s0)
    lqp_o, qs_o = make_runtime_optimizers(
        q9, ct.theta_c, seed_theta_p=ct.theta_p_sub,
        seed_theta_s=ct.theta_s_sub, weights=(0.9, 0.1))
    r_rt = run_with_aqe(q9, ct.theta_c, ct.theta_p0, ct.theta_s0,
                        lqp_optimizer=lqp_o, qs_optimizer=qs_o)
    assert r_rt.sim.actual_latency[0] <= r_ct.sim.actual_latency[0] * 1.2


def test_aggregation_min_threshold_rule(q9):
    m = q9.n_subqs
    tp = np.tile(default_theta(1)[1][0], (m, 1))
    ts = np.tile(default_theta(1)[2][0], (m, 1))
    join_ids = [sq.sq_id for sq in q9.subqs if sq.kind == "join"]
    tp[join_ids, 3] = [500.0 + i for i in range(len(join_ids))]  # huge s4
    p0, s0 = aggregate_submission_theta(q9, tp, ts)
    assert p0[3] == 10.0                     # capped at the Spark default
    tp[join_ids, 3] = 2.0
    p0, _ = aggregate_submission_theta(q9, tp, ts)
    assert p0[3] == 2.0                      # min across joins below cap


def test_baselines_nondominated(q9):
    obj = StageObjectives(q9)
    ev, D = obj.query_eval_coarse()
    F, U, dt, ne = solve_ws(ev, D, n_samples=800, seed=0)
    assert pareto_mask_np(F).all() and F.shape[0] >= 1
    F, U, dt, ne = solve_evo(ev, D, pop=24, n_evals=96, seed=0)
    assert pareto_mask_np(F).all()
    F, U, dt, ne = solve_pf(ev, D, n_points=5, n_probe=128, seed=0)
    assert pareto_mask_np(F).all()
    F1, _, _, _ = solve_so_fw(ev, D, np.array([0.9, 0.1]),
                              n_samples=400, seed=0)
    assert F1.shape == (1, 2)


def test_cluster_autotuner_prefers_latency_with_weight():
    from repro.cluster.autotune import autotune
    fast = autotune("qwen2-72b", "train_4k", weights=(0.95, 0.05))
    cheap = autotune("qwen2-72b", "train_4k", weights=(0.05, 0.95))
    assert fast.predicted[0] <= cheap.predicted[0]
    assert pareto_mask_np(fast.front).all()
