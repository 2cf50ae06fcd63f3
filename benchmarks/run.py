"""Benchmark orchestrator: one entry per paper table/figure (+ ours).

    PYTHONPATH=src python -m benchmarks.run [--only NAME[,NAME...]]
        [--bench tpch|tpcds|both] [--oracle] [--full]

Prints one CSV block per benchmark and writes JSON to results/bench/.
Exits non-zero when any benchmark it ran failed (the others still run and
print).

Benchmarks → paper artifacts:
  model_accuracy    Table 3      GTN+regressor WMAPE/P50/P90/Corr/Xput
  dag_aggregation   Fig 10(a,b)  HMOOC1/2/3 HV + solving time
  moo_comparison    Fig 10(c–e)  HMOOC3 vs WS/Evo/PF (fine-grained)
  granularity       Fig 10(f)    query-level baselines vs HMOOC3
  ws_coverage       Fig 4        WS front-collapse pathology
  end_to_end        Table 4      latency reduction @ (0.9, 0.1)
  adaptability      Table 5      preference sweep vs SO-FW
  pruning           §5.2         runtime-request pruning rates
  serve             (ours)       batched tuning-service throughput
  runtime           (ours)       batched runtime re-optimization service
  server            (ours)       streaming-admission server latency/throughput
  server_tenants    (ours)       multi-tenant fairness + per-tenant p99/Jain
  server_overload   (ours)       overload shedding: SLO classes past capacity
  server_model_solve (ours)      jitted model-backed solve vs legacy path
  server_scenarios  (ours)       nonstationary scenarios: elastic vs static
  server_fleet      (ours)       multi-worker fleet qps scaling + routing
  roofline          (ours)       per-cell dry-run roofline table
  cluster_autotune  (ours)       HMOOC on the JAX cluster itself
  kernels           (ours)       Pallas kernel microbenches
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from typing import Callable, Dict, List

from repro.compile_cache import setup_compile_cache

from .common import save_bench


def _print_rows(name: str, rows: List[dict]) -> None:
    print(f"\n=== {name} ===")
    if not rows:
        print("(no rows)")
        return
    keys = list(rows[0].keys())
    print(",".join(keys))
    for r in rows:
        print(",".join(str(r.get(k, "")) for k in keys))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--bench", default="tpch",
                    choices=["tpch", "tpcds", "both"])
    ap.add_argument("--oracle", action="store_true",
                    help="use simulator-on-estimates objectives (no models)")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    setup_compile_cache()

    benches = ["tpch", "tpcds"] if args.bench == "both" else [args.bench]
    use_model = not args.oracle
    nq = None if args.full else 10

    from . import bench_cluster, bench_end_to_end, bench_models, bench_moo, \
        bench_roofline, bench_runtime, bench_serve, bench_server
    from repro.core.moo.hmooc import HMOOCConfig

    registry: Dict[str, Callable[[], List[dict]]] = {
        "model_accuracy": lambda: bench_models.run_model_accuracy(
            ("tpch", "tpcds")),   # Table 3 covers both benchmarks
        "dag_aggregation": lambda: [r for b in benches for r in
                                    bench_moo.run_dag_aggregation(
                                        b, n_queries=nq or 22,
                                        use_model=use_model)],
        "moo_comparison": lambda: [r for b in benches for r in
                                   bench_moo.run_moo_comparison(
                                       b, n_queries=nq or 22, fine=True,
                                       use_model=use_model)],
        "granularity": lambda: [r for b in benches for r in
                                bench_moo.run_moo_comparison(
                                    b, n_queries=nq or 22, fine=False,
                                    use_model=use_model)],
        "ws_coverage": lambda: [r for b in benches for r in
                                bench_moo.run_ws_coverage(
                                    b, use_model=use_model)],
        "end_to_end": lambda: [r for b in benches for r in
                               bench_end_to_end.run_end_to_end(
                                   b, n_queries=None if args.full else 22,
                                   use_model=use_model)],
        "adaptability": lambda: [r for b in benches for r in
                                 bench_end_to_end.run_adaptability(
                                     b, n_queries=None if args.full else 22,
                                     use_model=use_model)],
        "pruning": lambda: [r for b in ("tpch", "tpcds") for r in
                            bench_end_to_end.run_pruning(b)],
        "serve": lambda: [bench_serve.run(
            b, HMOOCConfig(), [1, 8, 32], stream_len=64, seed=0)
            for b in benches],
        "runtime": lambda: [bench_runtime.run(
            b, n_queries=32 if args.full else 16) for b in benches],
        "server": lambda: [bench_server.run(
            b, n=64 if args.full else 32) for b in benches],
        "server_tenants": lambda: [bench_server.run_tenants(
            b, n=64 if args.full else 32) for b in benches],
        "server_overload": lambda: [bench_server.run_overload(
            b, n=96 if args.full else 48) for b in benches],
        "server_model_solve": lambda: [bench_server.run_model_solve(
            b, n_batches=4 if args.full else 2) for b in benches],
        # n_per_tenant=24 in both modes: shorter streams sit under the
        # pressure regime the elastic-vs-static comparison is sized for.
        "server_scenarios": lambda: [bench_server.run_scenarios(b)
                                     for b in benches],
        "server_fleet": lambda: [bench_server.run_fleet(
            b, n=96 if args.full else 48) for b in benches],
        "roofline": bench_roofline.run_roofline,
        "cluster_autotune": bench_cluster.run_cluster_autotune,
        "kernels": bench_cluster.run_kernels,
    }

    only = args.only.split(",") if args.only else list(registry)
    summary = {}
    for name in only:
        if name not in registry:
            print(f"unknown benchmark: {name}", file=sys.stderr)
            continue
        t0 = time.time()
        try:
            rows = registry[name]()
        except Exception as exc:  # noqa: BLE001 — report and continue
            print(f"\n=== {name} === FAILED: {type(exc).__name__}: {exc}")
            traceback.print_exc()
            summary[name] = "failed"
            continue
        _print_rows(name, rows)
        save_bench(name, rows)
        summary[name] = f"{len(rows)} rows, {time.time()-t0:.0f}s"
    print("\n=== summary ===")
    for k, v in summary.items():
        print(f"{k}: {v}")
    if "failed" in summary.values():
        sys.exit(1)


if __name__ == "__main__":
    main()
