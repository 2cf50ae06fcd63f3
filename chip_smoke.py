#!/usr/bin/env python3
"""One-chip smoke run of the model-backed OptimizerServer.

    python chip_smoke.py

Runs in one process, on one TPU, through the entry points a user calls.
The phases run in this order, and any failure raises (exit code != 0):

1. device  -- JAX's first device must be a TPU; a CPU fallback fails.
2. kernels -- ``pareto_filter``, ``ws_reduce`` and ``fused_ws_front``, each
   compiled for the chip (a Mosaic ``tpu_custom_call`` in the compiled
   program) at serving shapes, must equal their references.
3. models  -- the ``subq`` and ``qs`` PerfModels at their default widths,
   trained from seeded TPC-H traces with the benchmark's recipe
   (3 variants x 32 configurations, batch 512), for fewer steps than its
   1500.  Their embeddings and predictions on a
   fixed sample of trace rows must agree with the host CPU's, same
   parameters, to ``DEVICE_CPU_RTOL``.
4. serve   -- an OptimizerServer at ``HMOOCConfig()`` defaults serves a
   seeded Poisson TPC-H stream, then the TPC-DS templates with the most
   subQs.  Every request must finish ``served``, and each result must equal,
   bit for bit, the sequential reference (``compile_time_optimize`` +
   ``run_with_aqe`` with ``make_runtime_optimizers``) computed in the same
   process, on the same device, by separate model instances with the same
   parameters.

Earlier lines of standard output are diagnostics; their seconds come from
one smoke run and are not a benchmark.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import setup_compile_cache  # noqa: E402
from repro.core.models.perf_model import PerfModel  # noqa: E402
from repro.core.models.training import build_dataset, train_model  # noqa: E402
from repro.core.moo import hmooc, pareto  # noqa: E402
from repro.core.moo.hmooc import HMOOCConfig  # noqa: E402
from repro.core.tuning.compile_time import compile_time_optimize  # noqa: E402
from repro.core.tuning.runtime import make_runtime_optimizers  # noqa: E402
from repro.kernels import fused_solve as fused_pkg  # noqa: E402
from repro.kernels import pareto_filter as pareto_pkg  # noqa: E402
from repro.kernels import ws_reduce as ws_pkg  # noqa: E402
from repro.kernels.fused_solve import fused_ws_front, fused_ws_front_ref  # noqa: E402
from repro.kernels.pareto_filter.kernel import pareto_filter_pallas  # noqa: E402
from repro.kernels.pareto_filter.ref import pareto_mask_ref  # noqa: E402
from repro.kernels.ws_reduce.kernel import ws_reduce_pallas  # noqa: E402
from repro.kernels.ws_reduce.ref import ws_reduce_ref  # noqa: E402
from repro.queryengine.aqe import run_with_aqe  # noqa: E402
from repro.queryengine.trace import TraceSet, collect_traces  # noqa: E402
from repro.queryengine.workloads import (ArrivalModel, StreamRequest,  # noqa: E402
                                         default_workload, make_benchmark,
                                         serving_stream)
from repro.serve import (OptimizerServer, RuntimeSession, ServerConfig,  # noqa: E402
                         TuningService)

WEIGHTS = (0.9, 0.1)
SEED = 0
MODEL_STEPS = 300   # training steps per model (recipe: 1500)

# Largest relative difference allowed between the chip's and the host CPU's
# model outputs for the same parameters and inputs (see ``_rel_diff``).  On
# a TPU v5e, with the model matmuls at ``Precision.HIGHEST``, the largest
# was 9.2e-5 (predictions; embeddings 2.4e-5); at the TPU's default
# precision, which rounds matmul operands to bfloat16, the smallest was
# 2.1e-2.  The limit sits between the two, about ten times from each.
DEVICE_CPU_RTOL = 1e-3

# Serving shapes: the Pareto bucket above the 512-row routing threshold;
# the HMOOC2 bank (max_bank 48, 11 weights, 2 objectives) over a 64-subQ
# bucket; the fused aggregation's largest TPC-DS bucket (N 100 -> 128
# candidates, m 48 -> 64 subQs).
KERNEL_SHAPES = {"pareto_n": 1024, "ws_m": 64, "bank": 48, "n_weights": 11,
                 "fused_n": 100, "fused_m": 48}


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _tie_free_banks(rng: np.random.Generator, shape: Tuple[int, ...],
                    W: np.ndarray, margin: float = 1e-4) -> np.ndarray:
    """float32 banks whose weighted argmin is unambiguous for every weight.

    A bank whose best two weighted scores (float64, float32 weights) lie
    within ``margin`` is redrawn, so the MXU kernel and the float32
    reference cannot pick different rows by rounding alone.
    """
    *lead, B, k = shape
    flat = rng.random((int(np.prod(lead)), B, k)).astype(np.float32)
    W64 = W.astype(np.float32).astype(np.float64)
    while True:
        s = np.einsum("wk,nbk->wnb", W64, flat.astype(np.float64))
        two = np.partition(s, 1, axis=-1)[..., :2]
        bad = ((two[..., 1] - two[..., 0]) < margin).any(axis=0)
        if not bad.any():
            return flat.reshape(shape)
        flat[bad] = rng.random((int(bad.sum()), B, k)).astype(np.float32)


def _has_custom_call(jitted, *args, **kwargs) -> bool:
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return "tpu_custom_call" in text


def run_kernels(seed: int, *, interpret: bool,
                shapes: Dict[str, int] = KERNEL_SHAPES) -> Dict[str, object]:
    """Each kernel once at ``shapes`` against its reference; raises on any
    mismatch.  With ``interpret=False`` each must also compile to a Mosaic
    custom call.  References run on the host CPU device."""
    rng = np.random.default_rng(seed)
    cpu = jax.devices("cpu")[0]
    out: Dict[str, object] = {}
    nw, B, k = shapes["n_weights"], shapes["bank"], 2
    W = hmooc._ws_weights(nw).astype(np.float32)

    # pareto_filter: exact float32 compares, so any input must match.
    n = shapes["pareto_n"]
    F = rng.random((n, k)).astype(np.float32)
    valid = rng.random(n) > 0.1
    got = np.asarray(pareto_pkg.pareto_filter(
        jnp.asarray(F), jnp.asarray(valid), interpret=interpret))
    with jax.default_device(cpu):
        ref = np.asarray(pareto_mask_ref(jnp.asarray(F), jnp.asarray(valid)))
    if not np.array_equal(got, ref):
        raise AssertionError(f"pareto_filter: {int((got != ref).sum())} of "
                             f"{n} mask entries differ from pareto_mask_ref")
    out["pareto_filter"] = {"rows": n, "front": int(got.sum())}

    # ws_reduce: picks exact; scores (all in [0, 1]) to 1e-6 absolute: the
    # MXU's float32 matmul at HIGHEST precision is not rounded like the
    # host's IEEE multiply-add.
    m = shapes["ws_m"]
    Fb = _tie_free_banks(rng, (m, B, k), W)
    vals, idx = ws_pkg.ws_reduce(jnp.asarray(Fb), jnp.asarray(W),
                                 interpret=interpret)
    with jax.default_device(cpu):
        vr, ir = ws_reduce_ref(jnp.asarray(Fb), jnp.asarray(W))
    vals, idx, vr, ir = map(np.asarray, (vals, idx, vr, ir))
    if not np.array_equal(idx, ir):
        raise AssertionError(f"ws_reduce: {int((idx != ir).sum())} of "
                             f"{idx.size} picks differ from ws_reduce_ref")
    err = float(np.max(np.abs(vals - vr)))
    if err > 1e-6:
        raise AssertionError(f"ws_reduce: score error {err!r} > 1e-6")
    out["ws_reduce"] = {"m": m, "bank": B, "weights": nw,
                        "max_abs_score_err": err}

    # fused_ws_front: picks, float64 sums and the front mask all exact.
    N, mf = shapes["fused_n"], shapes["fused_m"]
    Fn = _tie_free_banks(rng, (N, mf, B, k), W)
    F_bank = Fn.astype(np.float64)
    jj, P_all, keep = fused_ws_front(Fn, F_bank, W)
    jr, Pr, kr = fused_ws_front_ref(Fn, F_bank, W)
    for name, a, b in (("picks", jj, jr), ("sums", P_all, Pr),
                       ("front mask", keep, kr)):
        if not np.array_equal(a, b):
            raise AssertionError(f"fused_ws_front: {name} differ from "
                                 f"fused_ws_front_ref")
    out["fused_ws_front"] = {"N": N, "m": mf, "front": int(keep.sum())}

    if not interpret:
        Np, mp = fused_pkg.ops._pow2(N, 32), fused_pkg.ops._pow2(mf, 4)
        compiled = {
            "pareto_filter": _has_custom_call(
                pareto_filter_pallas, jnp.asarray(F), jnp.asarray(valid),
                interpret=False),
            "ws_reduce": _has_custom_call(
                ws_reduce_pallas, jnp.asarray(Fb), jnp.asarray(W),
                interpret=False),
            "fused_ws_front": _has_custom_call(
                fused_pkg.ops._fused,
                jax.ShapeDtypeStruct((Np, mp, B, k), jnp.float32),
                jax.ShapeDtypeStruct((nw, k), jnp.float32), interpret=False),
        }
        if not all(compiled.values()):
            raise AssertionError(f"no tpu_custom_call in {compiled}")
        out["tpu_custom_call"] = compiled
    return out


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------

def smoke_traces(variants: int = 3, confs: int = 32) -> TraceSet:
    """Seeded TPC-H traces (3 variants x 32 configurations)."""
    queries = default_workload("tpch", variants, seed=SEED)
    return collect_traces(queries, confs, seed=SEED)


def train_models(traces: TraceSet, *, steps: int
                 ) -> Tuple[Dict[str, PerfModel], dict]:
    """subq + qs PerfModels at their default widths (``steps`` replaces the
    recipe's 1500)."""
    models, info = {}, {}
    for kind in ("subq", "qs"):
        ds, cfg = build_dataset(traces, kind, seed=SEED)
        models[kind] = train_model(ds, cfg, steps=steps, batch=512,
                                   seed=SEED)
        info[kind] = {"rows": ds.n, "gtn": dataclasses.asdict(cfg.gtn),
                      "hidden": list(cfg.hidden), "steps": steps}
    return models, info


def _model_outputs(model: PerfModel, pairs, theta: np.ndarray,
                   nond: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    model.embed_many(pairs)
    emb = np.stack([model.embed(q, s) for q, s in pairs])
    return emb, model.predict_rows(emb, theta, nond)


def _rel_diff(a: np.ndarray, ref: np.ndarray) -> float:
    """Largest |a - ref| in each column over the column's largest |ref|."""
    scale = np.max(np.abs(ref), axis=0)
    return float(np.max(np.abs(a - ref) / np.where(scale > 0, scale, 1.0)))


def device_vs_cpu(models: Dict[str, PerfModel], traces: TraceSet, *,
                  n_rows: int = 256) -> Dict[str, Dict[str, float]]:
    """Largest relative differences between the default device's and the
    host CPU's ``embed_many`` and ``predict_rows`` outputs, for the same
    parameters, on the first ``n_rows`` trace rows.  Raises when either
    exceeds ``DEVICE_CPU_RTOL``.

    Each output column is compared against its largest magnitude: single
    embedding components, and predictions clamped at zero, can sit at or
    near zero.
    """
    cpu = jax.devices("cpu")[0]
    rows = np.arange(min(n_rows, traces.query_idx.shape[0]))
    pairs = [(traces.queries[int(traces.query_idx[r])],
              int(traces.subq_idx[r])) for r in rows]
    out: Dict[str, Dict[str, float]] = {}
    for kind, model in models.items():
        ds, _ = build_dataset(traces, kind, seed=SEED)
        theta, nond = ds.theta[rows], ds.nond[rows]
        emb_d, pred_d = _model_outputs(_twin(model), pairs, theta, nond)
        host = PerfModel(model.cfg, params=jax.device_put(model.params, cpu),
                         target_stats=model.target_stats)
        with jax.default_device(cpu):
            emb_c, pred_c = _model_outputs(host, pairs, theta, nond)
        out[kind] = {"embed": _rel_diff(emb_d, emb_c),
                     "predict": _rel_diff(pred_d, pred_c)}
    log(f"[models] largest relative difference from the host CPU "
        f"(limit {DEVICE_CPU_RTOL}): {json.dumps(out)}")
    bad = {k: v for k, v in out.items()
           if not all(d <= DEVICE_CPU_RTOL for d in v.values())}
    if bad:
        raise AssertionError(f"model outputs differ from the host CPU's by "
                             f"more than {DEVICE_CPU_RTOL}: {bad}")
    return out


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def count_routes():
    """Count Pareto / weighted-sum routing decisions and kernel calls.

    Wraps the routing entry points for the duration: ``pareto_mask_fast``
    (every module that bound it), ``hmooc._ws_min_scores`` (read once per
    weighted-sum routing decision) and the three kernel wrappers.  The
    numpy route is the decisions that reached no kernel.
    """
    counts: Dict[str, int] = collections.Counter()
    patches = []

    def wrap(owner, name, key):
        orig = getattr(owner, name)

        def counted(*a, **kw):
            counts[key] += 1
            return orig(*a, **kw)
        patches.append((owner, name, orig))
        setattr(owner, name, counted)

    orig_fast = pareto.pareto_mask_fast
    for mod in list(sys.modules.values()):
        if getattr(mod, "__name__", "").startswith("repro.") and \
                getattr(mod, "pareto_mask_fast", None) is orig_fast:
            wrap(mod, "pareto_mask_fast", "pareto_decisions")
    wrap(hmooc, "_ws_min_scores", "ws_decisions")
    wrap(pareto_pkg, "pareto_filter", "pareto_kernel")
    wrap(ws_pkg, "ws_reduce", "ws_kernel")
    wrap(fused_pkg, "fused_ws_front", "ws_kernel")
    try:
        yield counts
    finally:
        for owner, name, orig in reversed(patches):
            setattr(owner, name, orig)


def _route_summary(c: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    return {"pareto": {"kernel": c["pareto_kernel"],
                       "numpy": c["pareto_decisions"] - c["pareto_kernel"]},
            "weighted_sum": {"kernel": c["ws_kernel"],
                             "numpy": c["ws_decisions"] - c["ws_kernel"]}}


def _twin(model: PerfModel) -> PerfModel:
    """Same parameters, own embedding memo and compiled functions."""
    return PerfModel(model.cfg, params=model.params,
                     target_stats=model.target_stats)


def _tpcds_requests(n: int, seed: int, t0: float) -> List[StreamRequest]:
    """The ``n`` TPC-DS templates with the most subQs, Poisson-timed."""
    qs = make_benchmark("tpcds")
    top = sorted(range(len(qs)), key=lambda t: (-qs[t].n_subqs, t))[:n]
    times = ArrivalModel(kind="poisson", rate_qps=4.0).draw(n, seed)
    return [StreamRequest(rid=i, query=qs[t], arrival_s=t0 + float(at))
            for i, (t, at) in enumerate(zip(top, times))]


def _reference(req: StreamRequest, msub: PerfModel, mqs: PerfModel,
               cfg: HMOOCConfig):
    q = req.query
    ct = compile_time_optimize(q, model=msub, weights=WEIGHTS, cfg=cfg)
    lqp_o, qs_o = make_runtime_optimizers(
        q, ct.theta_c, seed_theta_p=ct.theta_p_sub,
        seed_theta_s=ct.theta_s_sub, model_subq=msub, model_qs=mqs,
        weights=WEIGHTS)
    res = run_with_aqe(q, ct.theta_c, ct.theta_p0, ct.theta_s0,
                       lqp_optimizer=lqp_o, qs_optimizer=qs_o)
    return ct, res


_CT_FIELDS = ("front", "choice", "theta_c", "theta_p_sub", "theta_s_sub",
              "theta_p0", "theta_s0")
_AQE_FIELDS = ("theta_p_eff", "theta_s_eff", "final_join",
               "lqp_requests_sent", "qs_requests_sent", "requests_total")
_SIM_FIELDS = ("ana_latency", "actual_latency", "io_gb", "cost")


def _mismatches(served, ct, res) -> List[str]:
    pairs = [(f"ct.{f}", getattr(served.ct, f), getattr(ct, f))
             for f in _CT_FIELDS]
    pairs += [(f, getattr(served.result, f), getattr(res, f))
              for f in _AQE_FIELDS]
    pairs += [(f"sim.{f}", getattr(served.result.sim, f), getattr(res.sim, f))
              for f in _SIM_FIELDS]
    return [_describe(name, a, b) for name, a, b in pairs
            if not np.array_equal(a, b)]


def _describe(name: str, a, b) -> str:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or not np.issubdtype(a.dtype, np.number):
        return f"{name} (shape {a.shape} vs {b.shape})"
    return f"{name} (max abs diff {float(np.max(np.abs(a - b)))!r})"


def serve_and_check(models: Dict[str, PerfModel], *, cfg: HMOOCConfig,
                    n_tpch: int, n_tpcds: int, seed: int) -> dict:
    """Serve the streams, then hold every result to the sequential
    reference.  Raises unless all requests are ``served`` and equal."""
    msub, mqs = models["subq"], models["qs"]
    server = OptimizerServer(
        config=ServerConfig(),
        tuning=TuningService(model=msub, cfg=cfg),
        session=RuntimeSession(model_subq=msub, model_qs=mqs,
                               weights=WEIGHTS))
    streams = {"tpch": serving_stream(
        "tpch", n_tpch, seed=seed,
        arrivals=ArrivalModel(kind="poisson", rate_qps=8.0))}
    streams["tpcds"] = _tpcds_requests(n_tpcds, seed, 0.0)
    ref_sub, ref_qs = _twin(msub), _twin(mqs)
    out: dict = {"cfg": dataclasses.asdict(cfg)}
    compiles: Dict[str, int] = collections.Counter()
    with count_routes() as counts:
        served_all = []
        t0 = time.perf_counter()
        for name, reqs in streams.items():
            served = server.serve(reqs)
            compiles.update({
                k.split("@", 1)[1]: v
                for k, v in server.last_run.trace.counters.items()
                if k.startswith("compiles@")})
            statuses = collections.Counter(s.status for s in served)
            if statuses != {"served": len(reqs)}:
                raise AssertionError(f"{name}: statuses {dict(statuses)}")
            served_all += [(name, s) for s in served]
        out["served_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        bad = []
        for name, s in served_all:
            ct, res = _reference(s.request, ref_sub, ref_qs, cfg)
            diff = _mismatches(s, ct, res)
            if diff:
                bad.append(f"{name} rid {s.rid} ({s.request.query.qid}): "
                           f"{', '.join(diff)}")
        out["reference_s"] = time.perf_counter() - t0
    if bad:
        raise AssertionError("served results differ from the sequential "
                             "reference:\n  " + "\n  ".join(bad))
    out["requests"] = {name: len(r) for name, r in streams.items()}
    out["max_subqs"] = max(s.request.query.n_subqs for _, s in served_all)
    out["routes"] = _route_summary(counts)
    out["compile_stats"] = {"subq": msub.compile_stats(),
                            "qs": mqs.compile_stats()}
    out["compiles_by_span"] = dict(compiles)
    return out


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> int:
    phases: Dict[str, float] = {}

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    count = len(jax.devices())
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={count}")
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"{dev.platform!r}")
    if pareto_pkg.ops._default_interpret() or \
            ws_pkg.ops._default_interpret():
        raise SystemExit("chip_smoke: kernels would run in interpret mode")
    cache_dir = setup_compile_cache()
    phases["device"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    kern = run_kernels(SEED, interpret=False)
    phases["kernels"] = time.perf_counter() - t0
    log(f"[kernels] {json.dumps(kern)}")

    t0 = time.perf_counter()
    traces = smoke_traces()
    models, info = train_models(traces, steps=MODEL_STEPS)
    log(f"[models] steps cut to {MODEL_STEPS} per model "
        f"(recipe: 1500); {json.dumps(info)}")
    device_vs_cpu(models, traces)
    phases["models"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    srv = serve_and_check(models, cfg=HMOOCConfig(), n_tpch=16, n_tpcds=3,
                          seed=SEED)
    phases["serve"] = time.perf_counter() - t0
    log(f"[serve] all requests served and bit-identical to the sequential "
        f"reference: {json.dumps(srv['requests'])}, "
        f"max subQs {srv['max_subqs']}")
    log(f"[serve] served {srv['served_s']:.3f} s, reference "
        f"{srv['reference_s']:.3f} s (one smoke run, not a benchmark)")
    log(f"[routes] {json.dumps(srv['routes'])}")
    log(f"[compile_stats] {json.dumps(srv['compile_stats'])}")
    log(f"[compiles while serving, by span] "
        f"{json.dumps(srv['compiles_by_span'])}")
    log(f"[fused_solve.SEEN_BUCKETS] {sorted(fused_pkg.SEEN_BUCKETS)}")
    log(f"[phase seconds, one smoke run, not a benchmark] "
        f"{json.dumps(phases)}")
    log(f"[compile cache] {cache_dir}: {_cache_entries(cache_dir)} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
