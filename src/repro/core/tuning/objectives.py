"""Objective adapters: build stage/query evaluators for the MOO solvers.

Two backends expose the same interface:

* **model** — the trained subQ :class:`PerfModel` (the production path;
  sub-second solving via cached GTN embeddings + batched regressor);
* **oracle** — the analytic simulator evaluated on *CBO-estimated* inputs
  (what a perfect compile-time model would believe), used by tests and
  examples to isolate MOO behavior from model error.

Objectives (minimization), matching the paper's latency/cloud-cost pair:
  f1 = analytical latency (s)      — Σ over subQs at the query level
  f2 = cloud cost ($)              — latency·(core+mem rates) + IO·io rate

Both are *sums* over subQs for fixed θc, which is what licenses HMOOC's
list-structured DAG aggregation (paper §5.1.2).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

from ... import obs
from ...queryengine.plan import Query
from ...queryengine.simulator import CostModel, DEFAULT_COST, simulate_subq
from ...queryengine.trace import _alpha_stats
from ..models.perf_model import PerfModel, make_nondecision
from ..moo.hmooc import StageRows
from .spark_space import theta_c_space, theta_p_space, theta_s_space

__all__ = ["StageObjectives", "resource_rate", "QueryObjective",
           "fused_stage_eval", "StageRequest"]


def resource_rate(tc_raw: np.ndarray, cost: CostModel = DEFAULT_COST
                  ) -> np.ndarray:
    """$(per second) of the allocated cluster for raw θc rows."""
    k1, k2, k3 = tc_raw[:, 0], tc_raw[:, 1], tc_raw[:, 2]
    return (k1 * k3 * cost.price_core_h + k2 * k3 * cost.price_mem_gb_h) \
        / 3600.0


class StageObjectives:
    """stage_eval factory for one query (model- or oracle-backed)."""

    def __init__(self, query: Query, *, model: Optional[PerfModel] = None,
                 cost: CostModel = DEFAULT_COST):
        self.query = query
        self.model = model
        self.cost = cost
        self.cs = theta_c_space()
        self.ps = theta_p_space()
        self.ss = theta_s_space()
        self.d_c = self.cs.dim
        self.d_ps = self.ps.dim + self.ss.dim
        self.m = query.n_subqs
        if model is not None:
            # One batched GTN dispatch covers all subQs (a cache no-op when
            # the serving layer already prefetched the whole micro-batch).
            model.embed_many([(query, i) for i in range(self.m)])
            self._embs = [model.embed(query, i) for i in range(self.m)]
            self._nond = [make_nondecision(_alpha_stats(
                sq.est_input_rows, sq.est_input_bytes))
                for sq in query.subqs]

    # -- unit→raw helpers ----------------------------------------------------
    def snap_c(self, U: np.ndarray) -> np.ndarray:
        return self.cs.snap_unit(U)

    def snap_ps(self, U: np.ndarray) -> np.ndarray:
        out = U.copy()
        out[..., :self.ps.dim] = self.ps.snap_unit(U[..., :self.ps.dim])
        out[..., self.ps.dim:] = self.ss.snap_unit(U[..., self.ps.dim:])
        return out

    def split_raw(self, Tc: np.ndarray, Tps: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        tc = self.cs.to_raw(Tc)
        tp = self.ps.to_raw(Tps[..., :self.ps.dim])
        ts = self.ss.to_raw(Tps[..., self.ps.dim:])
        return tc, tp, ts

    # -- evaluators ------------------------------------------------------------
    def stage_eval(self, i: int, Tc: np.ndarray, Tps: np.ndarray
                   ) -> np.ndarray:
        """(n, d_c) ⊕ (n, d_ps) unit rows → (n, 2) [latency, cost]."""
        tc_raw, tp_raw, ts_raw = self.split_raw(Tc, Tps)
        if self.model is not None:
            theta = self.theta_rows(Tc, Tps)
            pred = self.model.predict(self._embs[i], theta, self._nond[i])
            lat, io = pred[:, 0], pred[:, 1]
        else:
            sim = simulate_subq(self.query.subqs[i], tc_raw, tp_raw, ts_raw,
                                cost=self.cost, aqe=True,
                                use_est_inputs=True)
            lat, io = sim.ana_latency, sim.io_gb
        dollars = lat * resource_rate(tc_raw, self.cost) \
            + io * self.cost.price_io_gb
        return np.stack([lat, dollars], -1)

    def theta_rows(self, Tc: np.ndarray, Tps: np.ndarray) -> np.ndarray:
        """Regressor θ layout for unit rows: [θc ⊕ θp ⊕ θs], float32."""
        return np.concatenate(
            [Tc, Tps[..., :self.ps.dim], Tps[..., self.ps.dim:]],
            -1).astype(np.float32)

    # -- flat query-level evaluators for the baselines -------------------------
    def query_eval_fine(self) -> Tuple[Callable[[np.ndarray], np.ndarray], int]:
        """Fine-grained flat space: θc ⊕ m × (θp ⊕ θs); D = d_c + m·d_ps."""
        D = self.d_c + self.m * self.d_ps

        def ev(U: np.ndarray) -> np.ndarray:
            n = U.shape[0]
            Tc = U[:, :self.d_c]
            total = np.zeros((n, 2))
            for i in range(self.m):
                lo = self.d_c + i * self.d_ps
                total += self.stage_eval(i, Tc, U[:, lo:lo + self.d_ps])
            return total
        return ev, D

    def query_eval_coarse(self) -> Tuple[Callable[[np.ndarray], np.ndarray], int]:
        """Query-level control: one shared θp ⊕ θs; D = d_c + d_ps."""
        D = self.d_c + self.d_ps

        def ev(U: np.ndarray) -> np.ndarray:
            n = U.shape[0]
            Tc = U[:, :self.d_c]
            Tps = U[:, self.d_c:]
            total = np.zeros((n, 2))
            for i in range(self.m):
                total += self.stage_eval(i, Tc, Tps)
            return total
        return ev, D


QueryObjective = Callable[[np.ndarray], np.ndarray]

# One stage-evaluation request: (objectives, subQ index, stage rows), or
# (objectives, subQ index, θc rows, θp⊕θs rows) with every row its own θc
# candidate.
StageRequest = Union[Tuple["StageObjectives", int, StageRows],
                     Tuple["StageObjectives", int, np.ndarray, np.ndarray]]


def _stage_rows(item: StageRequest) -> StageRows:
    if len(item) == 3:
        return item[2]
    Tc = item[2]
    return StageRows(Tc, np.arange(Tc.shape[0]), item[3])


def fused_stage_eval(items: Sequence[StageRequest]) -> List[np.ndarray]:
    """Evaluate many stage requests — across subQs *and* queries — at once.

    The model-backed path writes every request's regressor rows
    (per-row embedding ⊕ θ ⊕ nondecision) into a single bucket-padded
    :meth:`PerfModel.predict_rows` dispatch, then finishes the float64
    latency→dollars arithmetic per request.  Work that depends on θc alone
    is done once per distinct candidate set, not per row: each request's
    θ rows are written once per :class:`StageRows` object (the bank phase
    shares one across subQs), and the θc unit→raw conversion and resource
    rate once per (objectives, candidates) pair, gathered by row
    (counter ``solve.cost_rows``: θc rows converted).  Per-request outputs
    are identical to calling ``obj.stage_eval(i, Tc, Tps)`` one by one:
    row j of a padded batch equals row j of the per-request call, and the
    cost arithmetic is element-wise.  All requests must share one model
    (the serving layer calls it once per model); the oracle backend
    (``model is None``) falls back to per-request evaluation, which is
    already one vectorized simulator call each.
    """
    if not items:
        return []
    reqs = [(it[0], it[1], _stage_rows(it)) for it in items]
    model = reqs[0][0].model
    if model is None:
        return [obj.stage_eval(i, r.Tc, r.Tps) for obj, i, r in reqs]
    if any(obj.model is not model for obj, _, _ in reqs):
        raise ValueError("fused_stage_eval requires one shared model")
    obj0, i0, r0 = reqs[0]
    d_c = r0.cands.shape[1]
    total = sum(r.cidx.shape[0] for _, _, r in reqs)
    # Per-row emb/nond are broadcast straight into the dispatch buffers —
    # no per-request np.repeat intermediates on the host.
    emb_all = np.empty((total, obj0._embs[i0].shape[0]), np.float32)
    theta_all = np.empty((total, d_c + r0.Tps.shape[1]), np.float32)
    nond_all = np.empty((total, obj0._nond[i0].shape[0]), np.float32)
    first: dict = {}   # rows -> offset of its θ rows in theta_all
    per_c: dict = {}   # (obj, id(cands)) -> (f32 θc, $/s) per candidate
    rates = []
    off = 0
    for obj, i, r in reqs:
        n = r.cidx.shape[0]
        emb_all[off:off + n] = obj._embs[i]
        nond_all[off:off + n] = obj._nond[i]
        # repro: allow[RP004] within-call grouping token: rows sharing a candidate array share its conversion, outputs are identical either way, and the key never leaves this call
        key = (obj, id(r.cands))
        c = per_c.get(key)
        if c is None:
            c = per_c[key] = (r.cands.astype(np.float32),
                              resource_rate(obj.cs.to_raw(r.cands), obj.cost))
            obs.count("solve.cost_rows", r.cands.shape[0])
        src = first.get(r)
        if src is None:
            first[r] = off
            theta_all[off:off + n, :d_c] = c[0][r.cidx]
            theta_all[off:off + n, d_c:] = r.Tps
        else:
            theta_all[off:off + n] = theta_all[src:src + n]
        rates.append(c[1])
        off += n
    pred = model.predict_rows(emb_all, theta_all, nond_all)
    out: List[np.ndarray] = []
    off = 0
    for (obj, _, r), rate in zip(reqs, rates):
        n = r.cidx.shape[0]
        p = pred[off:off + n]
        off += n
        lat, io = p[:, 0], p[:, 1]
        dollars = lat * rate[r.cidx] + io * obj.cost.price_io_gb
        out.append(np.stack([lat, dollars], -1))
    return out
