#!/usr/bin/env python3
"""Offered-rate sweep of one cell, to find the rate its mix file fixes.

    python3 chipbench/sweep.py --workload <cell> --rates 4,8,12 --seconds 20 [--seed n]

One process, one set-up and warm-up; then for each rate a window of the
cell's mix with ``rate_qps`` set to that rate.
Prints one JSON line per rate: the latency percentiles, the served rate,
and the drain (last finish minus last arrival on the server's clock),
which grows with the window once a backlog builds.  The knee is the
highest rate whose ``solve_p95_s`` stays within the solve budget with no
growing drain; capacity is the plateau of ``served_qps``.  A composite configuration's
parts are resolved by name (``run.load_cell``), and the rate is the whole
stream's, shared among the parts as the mix says.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=12345)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.require_chip(spec["cell"]["chips"])
    run.setup_compile_cache()
    cell = run.Cell(spec)
    cell.setup()
    cell.warm(args.seed, args.seconds)
    for i, r in enumerate(float(x) for x in args.rates.split(",")):
        # A seed of its own per rate: a fresh mix must not meet the
        # response cache filled by the rate before.
        seed = args.seed + 1 + i
        mix = dict(cell.mix, rate_qps=r)
        stream = cell.stream(seed, args.seconds, mix=mix)
        obs = cell.window(stream, seed, None)
        served = obs["served"]
        e2e = run.end_to_end(served)
        drain = max(s.finished_s for s in served) - \
            max(s.arrival_s for s in served)
        st = cell.server.last_run
        print(json.dumps({"rate_qps": r, "seed": seed, "n": len(served),
                          **e2e, "batches": st.n_micro_batches,
                          "rounds": st.rounds,
                          "drain_s": drain, "wall_s": obs["wall_s"],
                          "compiles": obs["compiles"].get("compiles", 0)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
