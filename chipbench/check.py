"""What decides a run's ``correct``.

After the window has closed, a sample of its finished requests, drawn from
the run's seed and always holding the one with the most subQs of each
part of the configuration, is held to two references (each part's
requests with its own models; each reading the largest over the parts):

1. The plain reference of ``reference.py``, which imports nothing of the
   program, run op by op in ``jax.numpy`` float32 with every matrix product
   at ``Precision.HIGHEST`` (the configuration's precision) on the same
   device as the program (``REFERENCE``; the float64 numpy reference is
   read beside it and logged, not compared):

   * ``embed_gap``: the GTN embeddings that the window computed for every
     subQ of the sampled requests, against the reference's, both models;
     the largest difference in a column over the largest magnitude of that
     column;
   * ``head_gap``: a seeded sample of rows of every regressor dispatch of
     the window, against the reference head on the same inputs; the largest
     absolute difference of the z-space outputs (targets normalised to unit
     spread);
   * ``objective_gap``: the latency and dollars that each sampled answer
     gives its chosen configuration (``front[choice]``), against the
     reference model's objectives of that configuration; the largest
     relative difference.

2. ``sequential_diffs``: the program's own sequential path
   (``compile_time_optimize`` + ``run_with_aqe`` with
   ``make_runtime_optimizers``, separate model instances with the same
   weights), which every served result has to equal bit for bit: the
   compile-time front, choice and theta, and the runtime re-tuning's
   effective theta, final join, request counts, simulated latency, I/O and
   cost.  This holds the micro-batched serving path to the per-query one;
   it is the only check of the runtime re-tuning layer's answers.  The
   comparison helpers are copied from ``chip_smoke.py`` (``_mismatches``,
   ``_describe``, ``_reference``, ``_twin``).

Each number has its limit in ``LIMITS``; ``PERF.md`` gives the readings
each was set from.  ``model_gaps`` also reads the norm (``*_rel``) and
median (``*_med``) differences, which ``control.py`` prints beside them as
candidates for a number that separates a lower precision; they are not
compared.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import reference as R

# Each gap's limit lies between the largest reading of sound runs and the
# smallest of the control (the reference with three bfloat16 products per
# matrix product, ``Precision.HIGH``), with more room above the first;
# PERF.md gives the readings.  The served results must equal the sequential
# path's exactly.
REFERENCE = "f32"
LIMITS: Dict[str, float] = {
    "embed_gap": 6e-6,
    "head_gap": 6e-6,
    "objective_gap": 8e-6,
    "sequential_diffs": 0,
}

_CT_FIELDS = ("front", "choice", "theta_c", "theta_p_sub", "theta_s_sub",
              "theta_p0", "theta_s0")
_AQE_FIELDS = ("theta_p_eff", "theta_s_eff", "final_join",
               "lqp_requests_sent", "qs_requests_sent", "requests_total")
_SIM_FIELDS = ("ana_latency", "actual_latency", "io_gb", "cost")


def sample(served, k: int, seed: int) -> List:
    """``k`` finished requests drawn from ``seed``; for each benchmark
    served (``Query.benchmark``, one per part of the configuration) the
    request with the most subQs among them, first, longest first."""
    done = [s for s in served if s.status == "served" and s.ct is not None
            and s.result is not None]

    def size(s):
        return s.request.query.n_subqs, -s.rid
    longest: Dict[str, object] = {}
    for s in done:
        b = s.request.query.benchmark
        if b not in longest or size(s) > size(longest[b]):
            longest[b] = s
    first = sorted(longest.values(), key=size, reverse=True)
    rest = [s for s in done if all(s is not f for f in first)]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xC4EC]))
    pick = rng.choice(len(rest), size=max(0, min(k - len(first), len(rest))),
                      replace=False)
    return first + [rest[i] for i in sorted(pick)]


def _col_gap(a: np.ndarray, ref: np.ndarray) -> float:
    scale = np.max(np.abs(ref), axis=0)
    return float(np.max(np.abs(a - ref) / np.where(scale > 0, scale, 1.0)))


def _norm_gap(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def _med_gap(a: np.ndarray, ref: np.ndarray) -> float:
    scale = np.max(np.abs(ref), axis=0)
    return float(np.median(np.abs(a - ref) / np.where(scale > 0, scale, 1.0)))


def _f64(x):
    return np.asarray(x, np.float64)


def references(params: Dict[str, dict]) -> Dict[str, tuple]:
    """(arithmetic, parameters) of each reference the check can compare
    with: numpy float64 on the host (``f64``), and ``jax.numpy`` float32
    with every matrix product at ``Precision.HIGHEST`` on the default
    device (``f32``), the configuration's own precision."""
    import jax
    return {"f64": (R.arith(None),
                    {k: R.as_float64(p) for k, p in params.items()}),
            "f32": (R.arith(jax.lax.Precision.HIGHEST), params)}


def embed_outputs(arith, params: dict, queries, use_est: bool, n_heads: int):
    """Reference embeddings of every subQ of ``queries``."""
    g = R.stack_graphs([R.subq_graph(q, i, use_est=use_est)
                        for q in queries for i in range(q.n_subqs)])
    if isinstance(arith, R._Numpy):
        g = (_f64(g[0]), _f64(g[1]), _f64(g[2]), g[3])
    return np.asarray(R.embed(arith, params, *g, n_heads), np.float64)


def _inputs(arith, *rows):
    return tuple(_f64(x) if isinstance(arith, R._Numpy) else x for x in rows)


def model_gaps(sampled, models: Dict[str, object], heads: Dict[str, tuple],
               ref: tuple, stats: Dict[str, np.ndarray], cfg: dict,
               control: tuple = None) -> Dict[str, float]:
    """The three reference gaps of the program's outputs, or (with
    ``control``) of the reference computed that way in the program's
    place.  ``ref`` and ``control`` are (arithmetic, parameters) pairs."""
    n_heads = cfg["model"]["gtn"]["n_heads"]
    ra, rp = ref
    queries = [s.request.query for s in sampled]
    emb_gap = head_gap = emb_rel = head_rel = emb_med = head_med = 0.0
    for kind, model in models.items():
        use_est = kind == "subq"
        want = embed_outputs(ra, rp[kind]["gtn"], queries, use_est, n_heads)
        if control is None:
            got = np.stack([model.embed(q, i) for q in queries
                            for i in range(q.n_subqs)])
        else:
            got = embed_outputs(control[0], control[1][kind]["gtn"], queries,
                                use_est, n_heads)
        emb_gap = max(emb_gap, _col_gap(_f64(got), want))
        emb_rel = max(emb_rel, _norm_gap(_f64(got), want))
        emb_med = max(emb_med, _med_gap(_f64(got), want))
        rows = heads.get(kind)
        if rows is not None and len(rows[0]):
            e, t, d, z = rows
            zr = _f64(R.head(ra, rp[kind]["reg"], *_inputs(ra, e, t, d)))
            if control is not None:
                z = R.head(control[0], control[1][kind]["reg"], e, t, d)
            head_gap = max(head_gap,
                           float(np.max(np.abs(_f64(z) - zr))))
            head_rel = max(head_rel, _norm_gap(_f64(z), zr))
            head_med = max(head_med, _med_gap(_f64(z), zr))
    obj_gap = 0.0
    for s in sampled:
        q = s.request.query
        want = R.chosen_objectives(q, s.ct, rp["subq"], stats["subq"],
                                   cfg["cost"], n_heads, a=ra)
        if control is None:
            got = np.asarray(s.ct.front[s.ct.choice], np.float64)
        else:
            got = R.chosen_objectives(q, s.ct, control[1]["subq"],
                                      stats["subq"], cfg["cost"], n_heads,
                                      a=control[0])
        obj_gap = max(obj_gap, float(np.max(np.abs(got - want)
                                            / np.abs(want))))
    return {"embed_gap": emb_gap, "head_gap": head_gap,
            "objective_gap": obj_gap, "embed_rel": emb_rel,
            "head_rel": head_rel, "embed_med": emb_med, "head_med": head_med}


# -- the program's sequential path (copied from chip_smoke.py) ---------------

def _twin(model):
    """Same parameters, own embedding memo and compiled functions."""
    from repro.core.models.perf_model import PerfModel
    return PerfModel(model.cfg, params=model.params,
                     target_stats=model.target_stats)


def _reference(req, msub, mqs, hcfg, weights):
    from repro.core.tuning.compile_time import compile_time_optimize
    from repro.core.tuning.runtime import make_runtime_optimizers
    from repro.queryengine.aqe import run_with_aqe
    q = req.query
    ct = compile_time_optimize(q, model=msub, weights=weights, cfg=hcfg)
    lqp_o, qs_o = make_runtime_optimizers(
        q, ct.theta_c, seed_theta_p=ct.theta_p_sub,
        seed_theta_s=ct.theta_s_sub, model_subq=msub, model_qs=mqs,
        weights=weights)
    res = run_with_aqe(q, ct.theta_c, ct.theta_p0, ct.theta_s0,
                       lqp_optimizer=lqp_o, qs_optimizer=qs_o)
    return ct, res


def _describe(name: str, a, b) -> str:
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or not np.issubdtype(a.dtype, np.number):
        return f"{name} (shape {a.shape} vs {b.shape})"
    return f"{name} (max abs diff {float(np.max(np.abs(a - b)))!r})"


def _mismatches(served, ct, res) -> List[str]:
    pairs = [(f"ct.{f}", getattr(served.ct, f), getattr(ct, f))
             for f in _CT_FIELDS]
    pairs += [(f, getattr(served.result, f), getattr(res, f))
              for f in _AQE_FIELDS]
    pairs += [(f"sim.{f}", getattr(served.result.sim, f),
               getattr(res.sim, f)) for f in _SIM_FIELDS]
    return [_describe(name, a, b) for name, a, b in pairs
            if not np.array_equal(a, b)]


def sequential_diffs(sampled, models, hcfg, weights) -> Tuple[int, List[str]]:
    """Sampled requests whose served result differs from the sequential
    path's, and what differed."""
    msub, mqs = _twin(models["subq"]), _twin(models["qs"])
    bad = []
    for s in sampled:
        ct, res = _reference(s.request, msub, mqs, hcfg, weights)
        diff = _mismatches(s, ct, res)
        if diff:
            bad.append(f"rid {s.rid} ({s.request.query.qid}): "
                       f"{', '.join(diff)}")
    return len(bad), bad


def verdict(readings: Dict[str, float], limits: Dict[str, float] = LIMITS
            ) -> Tuple[bool, Dict[str, dict]]:
    """Every number within its limit (a NaN fails)."""
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in limits if k in readings}
    ok = len(checks) == len(limits) and all(
        v["value"] <= v["limit"] for v in checks.values())
    return ok, checks
