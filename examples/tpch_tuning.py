"""Workload tuning: all 22 TPC-H queries, HMOOC3+ vs default (Table 4 style).

    PYTHONPATH=src python examples/tpch_tuning.py [--model]

``--model`` first trains the GTN subQ model on seeded TPC-H traces (3
variants x 32 configurations, 1500 steps — minutes); the default uses
oracle objectives for a fast demonstration.
"""
import argparse

import numpy as np

from repro.core.models.training import build_dataset, train_model
from repro.core.moo.hmooc import HMOOCConfig
from repro.core.tuning.compile_time import compile_time_optimize
from repro.core.tuning.runtime import make_runtime_optimizers
from repro.queryengine.aqe import run_with_aqe
from repro.queryengine.simulator import default_theta
from repro.queryengine.trace import collect_traces
from repro.queryengine.workloads import default_workload, make_benchmark


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", action="store_true")
    ap.add_argument("--weights", default="0.9,0.1")
    args = ap.parse_args()
    w = tuple(float(x) for x in args.weights.split(","))

    model = None
    if args.model:
        traces = collect_traces(default_workload("tpch", 3, seed=0), 32,
                                seed=0)
        ds, mcfg = build_dataset(traces, "subq", seed=0)
        model = train_model(ds, mcfg, steps=1500, batch=512, seed=0)

    lat_d, lat_o, st = [], [], []
    for q in make_benchmark("tpch"):
        tc, tp, ts = default_theta(1)
        base = run_with_aqe(q, tc[0], tp[0], ts[0])
        ct = compile_time_optimize(q, model=model, weights=w,
                                   cfg=HMOOCConfig(dag_method="hmooc3"))
        lqp_o, qs_o = make_runtime_optimizers(
            q, ct.theta_c, seed_theta_p=ct.theta_p_sub,
            seed_theta_s=ct.theta_s_sub, model_subq=model, model_qs=model,
            weights=w)
        opt = run_with_aqe(q, ct.theta_c, ct.theta_p0, ct.theta_s0,
                           lqp_optimizer=lqp_o, qs_optimizer=qs_o)
        lat_d.append(base.sim.actual_latency[0])
        lat_o.append(opt.sim.actual_latency[0])
        st.append(ct.solve_time)
        red = 1 - lat_o[-1] / lat_d[-1]
        print(f"{q.qid}: {lat_d[-1]:7.2f}s → {lat_o[-1]:7.2f}s "
              f"({red:+.0%})  solve {st[-1]:.2f}s")

    lat_d, lat_o = np.array(lat_d), np.array(lat_o)
    print(f"\ntotal latency reduction: "
          f"{1 - lat_o.sum() / lat_d.sum():.0%} "
          f"(avg per-query {np.mean(1 - lat_o / lat_d):.0%}); "
          f"solve time avg {np.mean(st):.2f}s max {np.max(st):.2f}s")


if __name__ == "__main__":
    main()
