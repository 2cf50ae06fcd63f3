#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In one process, on the machine's first chip:

1. set-up: the cell's configuration (``configs/<config>.json``), the served
   models (trained once per checkout, see ``train.py``), the server;
2. warm-up: a short stream of the cell's own traffic, with a seed derived
   from ``--seed``, then every regressor bucket the window can use;
3. the window: ``OptimizerServer.serve`` over ``--seconds`` of the server's
   clock of the cell's traffic (``traffic/<mix>.json``);
4. the check of what the window produced (``check.py``);
5. one JSON line, the last of standard output.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (readers in ``metrics/``), read from a
profiler trace of the window.  Everything a cell needs is found by name
from ``BENCHMARK.json``.

The server's clock advances by the measured time of each flush and each
re-tuning round, and jumps only when nothing is queued or in flight; for
this single-threaded server that is an open loop in real time without the
sleeps, so the window takes less wall time than ``--seconds``.  Latencies
are on that clock, from each request's due arrival.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

CHECK_SAMPLE = 24        # requests held to the plain reference
SEQUENTIAL_SAMPLE = 8    # of those, requests held to the sequential path
TRACE_DIR = os.path.join(HERE, ".traces")
CACHE_DIR = os.path.join(HERE, ".jax_cache")
PEAKS = os.path.join(HERE, "peaks.json")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# What each part of a composite configuration brings: what sets one
# benchmark and its trained models.  The composite file sets the service.
PART_GROUPS = ("workload", "model", "training", "train_steps")
SERVICE_GROUPS = ("hmooc", "server", "weights", "cost")


def _read_config(bench: dict, name: str, root: str) -> Tuple[dict, str]:
    conf = next((c for c in bench["configs"] if c["name"] == name), None)
    if conf is None:
        raise SystemExit(f"chipbench: no configuration {name!r} in "
                         "BENCHMARK.json")
    with open(os.path.join(root, conf["file"])) as f:
        text = f.read()
    return json.loads(text), text


def load_config(bench: dict, name: str, root: str
                ) -> Tuple[dict, str, List[dict]]:
    """A configuration, its file's text, and its parts.

    A file without ``parts`` is one part of itself.  A file with ``parts``
    names two or more configurations of BENCHMARK.json, served by one
    server: each part brings ``PART_GROUPS`` in its own file, whose text
    keys its trained models (``train.trained_models``), so a composite
    serves the models its parts' own cells trained; the composite file sets
    the service (``SERVICE_GROUPS``).  The parts' ``model`` groups must be
    equal (the FLOP readers read one) and their benchmarks distinct (the
    server's models are keyed by ``Query.benchmark``).  Each part is
    ``{"name", "cfg", "cfg_text", "benchmark"}``."""
    cfg, text = _read_config(bench, name, root)
    if "parts" not in cfg:
        return cfg, text, [{"name": name, "cfg": cfg, "cfg_text": text,
                            "benchmark": cfg["workload"]["benchmark"]}]
    where = f"chipbench: composite configuration {name!r}"
    if len(cfg["parts"]) < 2:
        raise SystemExit(f"{where}: names {cfg['parts']}, not two parts or "
                         "more")
    own = [k for k in PART_GROUPS if k in cfg]
    lacks = [k for k in SERVICE_GROUPS if k not in cfg]
    if own or lacks:
        raise SystemExit(f"{where}: holds {own} (its parts' to give) and "
                         f"lacks {lacks} (its own to give)")
    parts = []
    for p in cfg["parts"]:
        pcfg, ptext = _read_config(bench, p, root)
        if "parts" in pcfg:
            raise SystemExit(f"{where}: its part {p!r} is composite too")
        parts.append({"name": p, "cfg": pcfg, "cfg_text": ptext,
                      "benchmark": pcfg["workload"]["benchmark"]})
    if any(p["cfg"]["model"] != parts[0]["cfg"]["model"] for p in parts):
        raise SystemExit(f"{where}: its parts' model groups differ "
                         f"({', '.join(p['name'] for p in parts)}); the FLOP "
                         "readers read one model group")
    benches = [p["benchmark"] for p in parts]
    if len(set(benches)) != len(benches):
        raise SystemExit(f"{where}: two parts serve one benchmark {benches}")
    return dict(cfg, model=parts[0]["cfg"]["model"]), text, parts


def load_cell(name: str, root: Optional[str] = None) -> dict:
    """The cell's entries of BENCHMARK.json and its configuration (with its
    parts, ``load_config``) and mix."""
    root = root or ROOT
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    cfg, cfg_text, parts = load_config(bench, cell["config"], root)
    with open(os.path.join(root, "chipbench", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    names = sorted(p["name"] for p in parts)
    if sorted(mix["parts"]) != names if "parts" in mix else len(names) > 1:
        raise SystemExit(f"chipbench: mix {cell['traffic']!r} shares "
                         f"{mix.get('parts')} among parts, configuration "
                         f"{cell['config']!r} has {names}")
    return {"bench": bench, "cell": cell, "cfg": cfg, "cfg_text": cfg_text,
            "parts": parts, "mix": mix}


def require_chip(chips: int):
    """JAX's first device, which must be a TPU with ``chips`` devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"chipbench: needs {chips} TPU chip(s); JAX has "
                         f"{len(devs)} {devs[0].platform!r} device(s)")
    from repro.kernels import pareto_filter, ws_reduce
    if pareto_filter.ops._default_interpret() or \
            ws_reduce.ops._default_interpret():
        raise SystemExit("chipbench: kernels would run in interpret mode")
    return devs[0]


def setup_compile_cache() -> str:
    """The program's persistent compilation cache
    (``repro.compile_cache``), given a fixed directory inside the checkout
    (the path is part of the cache's key) in ``JAX_COMPILATION_CACHE_DIR``,
    whatever the environment named: two checkouts never share compiled
    programs.  Every program is kept, however small or quick to compile,
    so that only a checkout's first run compiles."""
    import jax
    from repro.compile_cache import setup_compile_cache as program_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    # JAX read the variable when it was imported.
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Cell:
    """One configuration's served models and server, and its traffic.

    Each part (``load_config``) has its own ``subq`` and ``qs`` models,
    ``params`` and ``stats``.  ``models[kind]`` is what the server is given:
    the one part's ``PerfModel``, or with several parts a mapping of each
    part's benchmark (``Query.benchmark``) to its ``PerfModel``."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.cfg = spec["cfg"]
        self.mix = spec["mix"]
        self.parts = [dict(p) for p in spec["parts"]]

    def setup(self) -> Dict[str, float]:
        import jax
        from repro.core.models.gtn import GTNConfig
        from repro.core.models.perf_model import ModelConfig, PerfModel
        from repro.core.moo.hmooc import HMOOCConfig
        from repro.serve import (OptimizerServer, RuntimeSession,
                                 ServerConfig, TuningService)

        from chipbench.train import KINDS, trained_models

        cfg, mc = self.cfg, self.cfg["model"]
        gtn = GTNConfig(**mc["gtn"])
        secs: Dict[str, float] = {}
        for part in self.parts:
            params, stats, s = trained_models(part["cfg"], part["cfg_text"],
                                              log)
            secs = {k: secs.get(k, 0.0) + v for k, v in s.items()}
            part.update(params=params, stats=stats, models={})
            for kind in KINDS:
                mcfg = ModelConfig(kind=kind, theta_dim=mc["theta_dim"][kind],
                                   gtn=gtn, hidden=tuple(mc["hidden"]),
                                   n_targets=mc["n_targets"])
                part["models"][kind] = PerfModel(
                    mcfg, params=jax.device_put(params[kind]),
                    target_stats=stats[kind])
        if len(self.parts) == 1:
            self.models = self.parts[0]["models"]
        else:
            self.models = {kind: {p["benchmark"]: p["models"][kind]
                                  for p in self.parts} for kind in KINDS}
        self.hcfg = HMOOCConfig(**cfg["hmooc"])
        self.weights = tuple(cfg["weights"])
        self.server = OptimizerServer(
            config=ServerConfig(**cfg["server"]),
            tuning=TuningService(model=self.models["subq"], cfg=self.hcfg),
            session=RuntimeSession(model_subq=self.models["subq"],
                                   model_qs=self.models["qs"],
                                   weights=self.weights))
        return secs

    def stream(self, seed: int, seconds: float, *, warmup: bool = False,
               mix: Optional[dict] = None):
        from chipbench import traffic
        return traffic.stream({p["name"]: p["cfg"]["workload"]
                               for p in self.parts}, mix or self.mix, seed,
                              seconds, warmup=warmup)

    def warm(self, seed: int, seconds: float) -> None:
        """Serve the warm-up stream, then run every regressor bucket through
        every part's models.  A program that cannot serve the cell stops
        the run here, before anything is timed."""
        import traceback

        from repro.core.models import perf_model
        stream = self.stream(seed ^ 0x3A3A3A, seconds, warmup=True)
        try:
            self.server.serve(stream)
        except Exception as e:
            log(traceback.format_exc())
            raise SystemExit(
                f"chipbench: the program could not serve the warm-up of "
                f"{self.spec['cell']['name']} (parts "
                f"{[p['name'] for p in self.parts]}): "
                f"{type(e).__name__}: {e}") from e
        cap = perf_model._head_max_bucket()
        for m in (m for p in self.parts for m in p["models"].values()):
            d = m.cfg.gtn.d_model
            b = perf_model.MIN_DISPATCH_ROWS
            while b <= cap:
                m.predict_rows(np.zeros((b, d), np.float32),
                               np.zeros((b, m.cfg.theta_dim), np.float32),
                               np.zeros((b, 12), np.float32))
                b *= 2

    def window(self, stream, seed: int, trace_dir: Optional[str]):
        """Serve the window with the probes on; returns what it observed.
        ``heads`` holds each part's ``HeadRecorder`` per kind."""
        import jax
        from chipbench.probes import HeadRecorder, Probes, count_compiles

        probes = Probes(annotate=trace_dir is not None)
        probes.install(self.server, [(k, m) for p in self.parts
                                     for k, m in p["models"].items()])
        heads = {p["name"]: {k: HeadRecorder(m, seed + 2 * j + i)
                             for i, (k, m) in enumerate(p["models"].items())}
                 for j, p in enumerate(self.parts)}
        try:
            with count_compiles() as compiles:
                if trace_dir is not None:
                    jax.profiler.start_trace(trace_dir)
                t0 = time.perf_counter()
                try:
                    if trace_dir is not None:
                        with jax.profiler.TraceAnnotation("chipbench.window"):
                            served = self.server.serve(stream)
                    else:
                        served = self.server.serve(stream)
                    wall = time.perf_counter() - t0
                finally:
                    if trace_dir is not None:
                        jax.profiler.stop_trace()
        finally:
            probes.remove()
            for h in (h for hs in heads.values() for h in hs.values()):
                h.remove()
        return {"served": served, "wall_s": wall, "t0": t0,
                "probes": probes, "heads": heads,
                "compiles": dict(compiles)}


def _pct(x: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q))


def end_to_end(served) -> Dict[str, float]:
    """Latency percentiles over all requests (one not served counts as
    infinitely late) and finished requests per second of server clock."""
    inf = math.inf
    solve = [s.solve_latency_s if s.status == "served" else inf
             for s in served]
    plan = [s.plan_latency_s if s.status == "served" else inf
            for s in served]
    fin = [s.finished_s for s in served if s.status == "served"]
    first = min(s.arrival_s for s in served)
    span = (max(fin) - first) if fin else 0.0
    return {"solve_p50_s": _pct(solve, 50), "solve_p95_s": _pct(solve, 95),
            "plan_p95_s": _pct(plan, 95),
            "served_qps": len(fin) / span if span > 0 else 0.0}


def _tails(served, e2e) -> str:
    parts = []
    for name, attr in (("solve_p95_s", "solve_latency_s"),
                       ("plan_p95_s", "plan_latency_s")):
        v = [getattr(s, attr) if s.status == "served" else math.inf
             for s in served]
        parts.append(f"{name}: {len(v)} samples, "
                     f"{sum(x > e2e[name] for x in v)} beyond")
    return "; ".join(parts)


def read_per_layer(bench: dict, cell: str, run: dict) -> Dict[str, dict]:
    out = {}
    for m in bench["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        path = os.path.join(HERE, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "chipbench.metrics." + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def head_rows(obs: dict) -> Dict[str, dict]:
    """The kept regressor rows of the window, per part and kind."""
    return {name: {k: h.rows_out() for k, h in hs.items()}
            for name, hs in obs["heads"].items()}


def _of_part(sampled, part: dict) -> list:
    return [s for s in sampled
            if s.request.query.benchmark == part["benchmark"]]


def gaps_by_reference(cell: Cell, sampled, heads: Dict[str, dict],
                      control: Optional[str] = None
                      ) -> Dict[str, Dict[str, float]]:
    """``check.model_gaps`` against each of ``check.references``: each part's
    sampled requests with its own models, parameters, stats and head rows;
    each reading the largest over the parts (a NaN wins).  With
    ``control`` (a ``reference.arith`` name) the reference computed that
    way stands in the program's place."""
    from chipbench import check
    from chipbench import reference as R
    out: Dict[str, Dict[str, float]] = {}
    for p in cell.parts:
        mine = _of_part(sampled, p)
        if not mine:
            continue
        ctl = None if control is None else (R.arith(control), p["params"])
        for name, ref in check.references(p["params"]).items():
            got = check.model_gaps(mine, p["models"], heads[p["name"]], ref,
                                   p["stats"], cell.cfg, control=ctl)
            worst = out.setdefault(name, got)
            for k, v in got.items():
                if v != v or v > worst[k]:
                    worst[k] = v
    return out


def check_run(cell: Cell, obs: dict, seed: int) -> Dict[str, float]:
    from chipbench import check
    sampled = check.sample(obs["served"], CHECK_SAMPLE, seed)
    heads = head_rows(obs)
    by_ref = gaps_by_reference(cell, sampled, heads)
    readings = by_ref.get(check.REFERENCE, {})
    for name, got in by_ref.items():
        if name != check.REFERENCE:
            log(f"[check] against {name}: " + json.dumps(got))
    n_bad, bad = 0, []
    for p in cell.parts:
        mine = _of_part(sampled[:SEQUENTIAL_SAMPLE], p)
        if mine:
            n, lines = check.sequential_diffs(mine, p["models"], cell.hcfg,
                                              cell.weights)
            n_bad, bad = n_bad + n, bad + lines
    for line in bad:
        log(f"[check] differs from the sequential path: {line}")
    readings["sequential_diffs"] = n_bad
    rows = {name: {k: 0 if v is None else len(v[0]) for k, v in h.items()}
            for name, h in heads.items()}
    log(f"[check] {len(sampled)} requests held to the reference, "
        f"{min(len(sampled), SEQUENTIAL_SAMPLE)} to the sequential path; "
        f"head rows {rows}")
    return readings


def main(argv=None, root: Optional[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_cell(args.workload, root)
    dev = require_chip(spec["cell"]["chips"])
    import jax
    cache = setup_compile_cache()
    with open(PEAKS) as f:
        peaks = json.load(f)
    if dev.device_kind not in peaks:
        raise SystemExit(f"chipbench: no peaks for {dev.device_kind!r}")
    log(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache}")

    cell = Cell(spec)
    t0 = time.perf_counter()
    secs = cell.setup()
    secs["models_and_server"] = time.perf_counter() - t0 - sum(secs.values())
    t0 = time.perf_counter()
    cell.warm(args.seed, args.seconds)
    secs["warmup"] = time.perf_counter() - t0
    stream = cell.stream(args.seed, args.seconds)
    secs["jax_start_and_imports"] = t0 - T_START - sum(
        v for k, v in secs.items() if k != "warmup")
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(TRACE_DIR, f"{args.workload}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    obs = cell.window(stream, args.seed, trace_dir)
    setup_s = obs["t0"] - T_START
    served = obs["served"]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    server_span = max(s.finished_s for s in served) - \
        min(s.arrival_s for s in served)
    log(f"[setup] {setup_s:.3f} s: {json.dumps(secs)}")
    log(f"[window] {len(served)} requests over {args.seconds} s of server "
        f"clock; serve() wall {obs['wall_s']:.3f} s; window/wall "
        f"{args.seconds / obs['wall_s']:.3f}; server span "
        f"{server_span:.3f} s")
    log(f"[window] compiles inside the window: {json.dumps(obs['compiles'])}")
    st = cell.server.last_run
    log(f"[window] micro-batches {st.n_micro_batches}, rounds {st.rounds}, "
        f"solved {cell.server.tuning.totals.n_solved} in total, "
        f"probes {json.dumps(dict(obs['probes'].counts))}")
    e2e = end_to_end(served)
    log(f"[e2e] {json.dumps(e2e)}; {_tails(served, e2e)}")

    bench = spec["bench"]
    names = [m["name"] for m in bench["end_to_end"]
             if args.workload in m.get("workloads", [args.workload])]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    result: dict = {}
    if args.trace:
        from chipbench import trace_reduce
        tr = trace_reduce.reduce(*trace_reduce.load(
            trace_reduce.find_trace(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"[trace] busy {tr['busy_s']} s of {tr['window_s']} s; "
            f"programs {json.dumps(tr['programs'])}; "
            f"host spans {json.dumps(tr['host_spans_s'])}")
        run = {"served": served, "probes": obs["probes"], "trace": tr,
               "cfg": cell.cfg, "peak": peaks[dev.device_kind]}
        metrics = read_per_layer(bench, args.workload, run)
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {"setup_s": {"value": setup_s, "unit": units["setup_s"]}}
        metrics.update({n: {"value": e2e[n], "unit": units[n]}
                        for n in names if n != "setup_s"})

    from chipbench import check
    readings = check_run(cell, obs, args.seed)
    ok, checks = check.verdict(readings)
    n_failed = sum(s.status != "served" for s in served)
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps({"correct": bool(ok), "attempted": len(served),
                      "failed": n_failed, "metrics": metrics,
                      "device": device, **result, "checks": checks}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
