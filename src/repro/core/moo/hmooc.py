"""Hierarchical MOO with Constraints (paper §5.1, Algorithms 1–4).

Solves the compile-time fine-grained tuning problem

    argmin_{θc, {θp_i}, {θs_i}}  [ Σ_i φ_1(subQ_i, θc, θp_i, θs_i),
                                   Σ_i φ_2(subQ_i, θc, θp_i, θs_i) ]

by (1) *subQ tuning* — Algorithm 1's effective-set generation with θc
clustering, per-representative θp MOO over a shared sample pool, optimal-θp
assignment to cluster members, and crossover-based θc enrichment — and
(2) *DAG aggregation* — HMOOC1 (exact divide-and-conquer Minkowski merge),
HMOOC2 (weighted-sum over functions), HMOOC3 (boundary/extreme-point
approximation), exploiting that analytical latency and cost are sums over
subQs so the DAG reduces to a list.

The stage evaluator abstracts the objective model:

    stage_eval(i, Tc, Tps) -> (n, k) objective rows for subQ i,
        Tc: (n, d_c) unit-space θc, Tps: (n, d_p + d_s) unit-space θp⊕θs.

In production it wraps the trained subQ PerfModel; tests can plug the
analytic simulator or synthetic functions.

Hot paths are array-level: every stage_eval call covers a whole
representative set or candidate population at once (m calls per phase
instead of C·m), dominance masks route through the Pallas ``pareto_filter``
kernel above the small-n threshold (``pareto_mask_fast``) and below it a
phase's C·m banks are filtered in one vectorized pass, and HMOOC2's
per-weight bank argmin runs on the ``ws_reduce`` kernel when enabled.

The candidate-sampling half of Algorithm 1 (LHS, clustering, crossover) is
query-independent; :class:`EffectiveSet` captures it — together with the
per-representative optimal-θp banks — so a serving layer can reuse it across
repeated-template traffic (see ``repro.serve``).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ... import obs
from .clustering import kmeans_fit
from . import pareto as _pareto
from .pareto import _f32_tie_hazard, pareto_mask_fast, pareto_mask_np

__all__ = ["HMOOCConfig", "HMOOCResult", "EffectiveSet", "hmooc_solve",
           "HmoocPlan", "StageRows", "build_candidates",
           "dag_aggregate", "minkowski_merge_2d"]

StageEval = Callable[[int, np.ndarray, np.ndarray], np.ndarray]

# Score-matrix volume (N·m·B·nw) above which HMOOC2 uses the ws_reduce
# Pallas kernel.  CPU hosts default to the float64 numpy einsum (exact and
# faster than interpret mode); TPU routes to the MXU kernel.  None =
# resolve lazily from the env var / backend (tests monkeypatch directly).
_WS_MIN_SCORES = None


def _ws_min_scores() -> int:
    if _WS_MIN_SCORES is not None:
        return _WS_MIN_SCORES
    return int(os.environ.get(
        "REPRO_WS_KERNEL_MIN_SCORES",
        str(1 << 18) if _pareto.backend() == "tpu" else str(1 << 60)))


@dataclasses.dataclass(frozen=True)
class HMOOCConfig:
    n_c_init: int = 64          # initial θc candidates (LHS)
    n_clusters: int = 10        # θc clusters (Alg. 1 line 2)
    n_p_pool: int = 256         # shared θp⊕θs sample pool size
    n_c_enrich: int = 64        # crossover-generated θc candidates
    max_bank: int = 48          # per-(θc, subQ) Pareto bank cap
    dag_method: str = "hmooc3"  # "hmooc1" | "hmooc2" | "hmooc3"
    n_ws_weights: int = 11      # weight vectors for hmooc2
    seed: int = 0


@dataclasses.dataclass
class EffectiveSet:
    """Reusable Algorithm 1 artifacts.

    ``Uc``/``labels``/``reps``/``pool`` depend only on the parameter spaces
    and :class:`HMOOCConfig` (the rng never touches the query), so they are
    valid for *any* query.  ``opt_idx`` (per-representative per-subQ
    Pareto-optimal pool indices) is computed from one query's statistics;
    reusing it is exact for an identical query and a template-level
    approximation otherwise.
    """
    Uc: np.ndarray                                 # (N, d_c) θc candidates
    labels: np.ndarray                             # (N,) cluster ids
    reps: np.ndarray                               # (C, d_c) representatives
    pool: np.ndarray                               # (P, d_ps) θp⊕θs samples
    opt_idx: Optional[List[List[np.ndarray]]] = None   # [C][m] pool indices
    k_obj: int = 2

    def without_banks(self) -> "EffectiveSet":
        return dataclasses.replace(self, opt_idx=None)


@dataclasses.dataclass
class HMOOCResult:
    front: np.ndarray           # (q, k) query-level Pareto objective values
    theta_c: np.ndarray         # (q, d_c) unit
    theta_ps: np.ndarray        # (q, m, d_ps) unit per-subQ θp⊕θs
    solve_time: float
    n_evals: int
    extras: Dict[str, float]
    effective_set: Optional[EffectiveSet] = None


# ---------------------------------------------------------------------------
# Subquery tuning (Algorithm 1)
# ---------------------------------------------------------------------------

def _snap_unique(U: np.ndarray, snap) -> np.ndarray:
    Us = snap(U) if snap is not None else U
    return np.unique(np.round(Us, 9), axis=0)


def _crossover(Uc: np.ndarray, n_new: int, d: int,
               rng: np.random.Generator) -> np.ndarray:
    """θc crossover (App. C.1): random cut + Cartesian-product recombination."""
    if Uc.shape[0] < 2:
        return np.zeros((0, d))
    out = []
    for _ in range(4):  # a few cut positions
        cut = int(rng.integers(1, d))
        pre = np.unique(Uc[:, :cut], axis=0)
        suf = np.unique(Uc[:, cut:], axis=0)
        ii = rng.integers(0, pre.shape[0], size=n_new)
        jj = rng.integers(0, suf.shape[0], size=n_new)
        out.append(np.concatenate([pre[ii], suf[jj]], axis=1))
    cand = np.unique(np.concatenate(out, 0), axis=0)
    rng.shuffle(cand)
    return cand[:n_new]


def _pareto_bank(F: np.ndarray, cap: int) -> np.ndarray:
    """Indices of the non-dominated rows of F (capped, best-first)."""
    mask = pareto_mask_fast(F)
    idx = np.nonzero(mask)[0]
    if idx.size > cap:
        idx = _spread(F, idx, cap)
    return idx


def _spread(F: np.ndarray, idx: np.ndarray, cap: int) -> np.ndarray:
    """``cap`` of the rows ``idx`` of F: sorted by the first objective,
    evenly spaced."""
    order = idx[np.argsort(F[idx, 0])]
    keep = np.linspace(0, order.size - 1, cap).round().astype(int)
    return order[keep]


def _pareto_banks_2d(Fs: Sequence[np.ndarray], C: int, P: int, cap: int
                     ) -> Tuple[List[np.ndarray], int]:
    """:func:`_pareto_bank` of every two-objective bank, in one pass.

    ``Fs`` holds m arrays of shape ``(C·P, 2)``; bank ``i·C + r`` is rows
    ``r·P:(r+1)·P`` of ``Fs[i]``.  Returns each bank's indices, equal array
    for array to :func:`_pareto_bank`'s when its mask takes the float64
    path, and the number of banks over ``cap``, which take its
    :func:`_spread`.

    The mask follows :func:`~.pareto._pareto_mask_2d_np`: sorted by f0, a
    row survives when its f1 is the least of its equal-f0 group and below
    every f1 of a smaller f0.  A row alone in its group needs only the
    prefix minimum; the groups of tied f0 are settled apart.
    """
    R = len(Fs) * C
    if R * P == 0:
        return [np.zeros(0, np.intp) for _ in range(R)], 0
    F = np.stack(Fs).astype(np.float64, copy=False).reshape(R * P, 2)
    f0, f1 = np.ascontiguousarray(F.T).reshape(2, R, P)
    valid = np.isfinite(f0) & np.isfinite(f1)
    if not valid.all():
        # Invalid rows sort last with f1 = inf: they never survive nor
        # lower a valid row's prefix minimum.
        f0 = np.where(valid, f0, np.inf)
        f1 = np.where(valid, f1, np.inf)
    flat = (np.argsort(f0, axis=1) + P * np.arange(R)[:, None]).ravel()
    s0 = f0.ravel()[flat].reshape(R, P)
    s1 = f1.ravel()[flat].reshape(R, P)
    prev = np.empty((R, P))          # least f1 before each sorted row
    prev[:, 0] = np.inf
    np.minimum.accumulate(s1[:, :-1], axis=1, out=prev[:, 1:])
    keep = s1 < prev
    tied = np.zeros((R, P), bool)    # same f0 as the row before
    np.equal(s0[:, 1:], s0[:, :-1], out=tied[:, 1:])
    if tied.any():
        in_grp = tied.copy()
        in_grp[:, :-1] |= tied[:, 1:]
        t = np.flatnonzero(in_grp)
        st = np.flatnonzero(~tied.ravel()[t])
        g1 = s1.ravel()[t]
        gmin = np.minimum.reduceat(g1, st)
        gmin[gmin >= prev.ravel()[t[st]]] = np.nan
        keep.ravel()[t] = g1 == np.repeat(gmin, np.diff(st, append=t.size))
    surv = np.flatnonzero(keep.ravel())      # by bank, then by f0
    n = np.bincount(surv // P, minlength=R)
    ends = np.cumsum(n).tolist()
    kept = np.sort(flat[surv]) % P
    out = [kept[a:b] for a, b in zip([0] + ends[:-1], ends)]
    big = np.flatnonzero(n > cap).tolist()
    for b in big:
        i, r = divmod(b, C)
        out[b] = _spread(Fs[i][r * P:(r + 1) * P], out[b], cap)
    return out, len(big)


def _lhs(rng: np.random.Generator, n: int, d: int) -> np.ndarray:
    u = (rng.permuted(np.tile(np.arange(n), (d, 1)), axis=1).T
         + rng.random((n, d))) / n
    return u


def build_candidates(
    d_c: int,
    d_ps: int,
    cfg: HMOOCConfig,
    *,
    snap_c=None,
    snap_ps=None,
    rng: Optional[np.random.Generator] = None,
) -> EffectiveSet:
    """Query-independent half of Algorithm 1: θc candidates + θp⊕θs pool.

    Covers lines 1–2 plus the crossover enrichment of lines 5–6 (the rng
    stream is never consumed by stage evaluation, so sampling the enriched
    set up front is identical to interleaving it with the evaluations).
    """
    rng = rng or np.random.default_rng(cfg.seed)
    # Line 1: init_c (LHS over the unit cube, snapped to valid raw values).
    Uc0 = _lhs(rng, cfg.n_c_init, d_c)
    Uc0 = _snap_unique(Uc0, snap_c)
    # Line 2: cluster.
    km, labels0 = kmeans_fit(Uc0, cfg.n_clusters, rng)
    reps = km.centers
    if snap_c is not None:
        reps = snap_c(reps)
    # Shared θp⊕θs pool.
    pool = _lhs(rng, cfg.n_p_pool, d_ps)
    if snap_ps is not None:
        pool = snap_ps(pool)
    # Lines 5-6: enrich via crossover, assign to existing clusters.
    Uc1 = _crossover(Uc0, cfg.n_c_enrich, d_c, rng)
    if snap_c is not None and Uc1.size:
        Uc1 = _snap_unique(Uc1, snap_c)
    if Uc1.size:
        # Drop duplicates of the initial set.
        dup = (Uc1[:, None, :] == Uc0[None, :, :]).all(-1).any(1)
        Uc1 = Uc1[~dup]
    if Uc1.size:
        labels1 = km.assign(Uc1)
        Uc = np.concatenate([Uc0, Uc1], 0)
        labels = np.concatenate([labels0, labels1], 0)
    else:
        Uc, labels = Uc0, labels0
    return EffectiveSet(Uc=Uc, labels=labels, reps=reps, pool=pool)


@dataclasses.dataclass(frozen=True, eq=False)
class StageRows:
    """One subQ's stage rows in a solve phase, by θc candidate.

    Row j is θc = ``cands[cidx[j]]`` ⊕ θp⊕θs = ``Tps[j]``.  A phase's rows
    repeat a handful of θc candidates, so work that depends on θc alone is
    done once per candidate and gathered by ``cidx``; the bank phase hands
    one object to every subQ.
    """
    cands: np.ndarray      # (C, d_c) unit θc candidates
    cidx: np.ndarray       # (n,) each row's candidate
    Tps: np.ndarray        # (n, d_ps) unit θp⊕θs rows

    @property
    def Tc(self) -> np.ndarray:
        """(n, d_c) unit θc rows."""
        return self.cands[self.cidx]


def _rep_bank_rows(eset: EffectiveSet) -> StageRows:
    """The stage rows of the representative-MOO phase, shared by every subQ."""
    C, P = eset.reps.shape[0], eset.pool.shape[0]
    return StageRows(eset.reps, np.repeat(np.arange(C), P),
                     np.tile(eset.pool, (C, 1)))


def _rep_banks(Fs: Sequence[np.ndarray], eset: EffectiveSet,
               cfg: HMOOCConfig) -> Tuple[List[List[np.ndarray]], int, int]:
    """Line 3's banks from each subQ's objectives on :func:`_rep_bank_rows`.

    Returns (opt_idx [C][m], k_obj, n_evals).
    """
    C, P = eset.reps.shape[0], eset.pool.shape[0]
    opt_idx: List[List[np.ndarray]] = [[] for _ in range(C)]
    k_obj = 2
    n_evals = 0
    thr = _pareto._KERNEL_MIN_N
    if thr is None:
        thr = _pareto._default_kernel_min_n()
    if P < thr and all(F.shape[1] == 2 for F in Fs):
        # Every bank takes pareto_mask_fast's float64 path: filter them all
        # at once.  Larger banks keep the per-bank kernel routing.
        banks, n_capped = _pareto_banks_2d(Fs, C, P, cfg.max_bank)
        obs.count("hmooc.banks.batched", len(banks))
        obs.count("hmooc.banks.capped", n_capped)
        for b, idx in enumerate(banks):
            opt_idx[b % C].append(idx)
        return opt_idx, k_obj, sum(F.shape[0] for F in Fs)
    for F in Fs:
        n_evals += F.shape[0]
        k_obj = F.shape[1]
        Fr = F.reshape(C, P, k_obj)
        for r in range(C):
            opt_idx[r].append(_pareto_bank(Fr[r], cfg.max_bank))
    return opt_idx, k_obj, n_evals


def _optimize_rep_banks(
    stage_eval: StageEval,
    m: int,
    eset: EffectiveSet,
    cfg: HMOOCConfig,
) -> Tuple[List[List[np.ndarray]], int, int]:
    """Line 3: per-representative θp MOO, batched to one eval per subQ.

    Returns (opt_idx [C][m], k_obj, n_evals).
    """
    rows = _rep_bank_rows(eset)
    Tc = rows.Tc
    return _rep_banks([stage_eval(i, Tc, rows.Tps) for i in range(m)],
                      eset, cfg)


AssignRequest = Tuple[StageRows, List[Tuple[np.ndarray, np.ndarray]]]


def _assign_requests(m: int, eset: EffectiveSet, cfg: HMOOCConfig
                     ) -> List[Optional[AssignRequest]]:
    """Per-subQ (stage rows, scatter chunks) of the assign phase.

    Entry i is None when subQ i has nothing to evaluate (no members or all
    banks empty).  The rows' candidates are ``eset.Uc``; chunk
    ``(members, sel)`` covers every member of one cluster against its
    representative's bank ``sel``, member-major.
    """
    Uc, labels, pool = eset.Uc, eset.labels, eset.pool
    opt_idx = eset.opt_idx
    assert opt_idx is not None
    C = eset.reps.shape[0]
    B = cfg.max_bank
    members_by_rep = [np.nonzero(labels == r)[0] for r in range(C)]
    out: List[Optional[AssignRequest]] = []
    for i in range(m):
        rows_c: List[np.ndarray] = []
        rows_p: List[np.ndarray] = []
        chunks: List[Tuple[np.ndarray, np.ndarray]] = []
        for r in range(C):
            members = members_by_rep[r]
            sel = opt_idx[r][i] if i < len(opt_idx[r]) else np.zeros(0, int)
            if members.size == 0 or sel.size == 0:
                continue
            sel = sel[:min(sel.size, B)]
            rows_c.append(np.repeat(members, sel.size))
            rows_p.append(np.tile(sel, members.size))
            chunks.append((members, sel))
        if not chunks:
            out.append(None)
            continue
        out.append((StageRows(Uc, np.concatenate(rows_c),
                              pool[np.concatenate(rows_p)]), chunks))
    return out


def _assign_scatter(
    reqs: Sequence[Optional[AssignRequest]],
    Fs: Sequence[np.ndarray],
    N: int,
    cfg: HMOOCConfig,
    k_obj: int,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Lines 4/7's banks: scatter each subQ's objectives on its
    :func:`_assign_requests` rows into ``(F_bank, idx_bank)``.

    ``Fs`` holds one array per non-None request, in subQ order.
    """
    m, B = len(reqs), cfg.max_bank
    F_bank = np.full((N, m, B, k_obj), np.inf)
    idx_bank = np.full((N, m, B), -1, int)
    n_evals = 0
    it = iter(Fs)
    for i, req in enumerate(reqs):
        if req is None:
            continue
        F = next(it)
        n_evals += F.shape[0]
        off = 0
        for members, sel in req[1]:
            nb = sel.size
            cnt = members.size * nb
            F_bank[members, i, :nb] = \
                F[off:off + cnt].reshape(members.size, nb, k_obj)
            idx_bank[members, i, :nb] = sel
            off += cnt
    return F_bank, idx_bank, n_evals


def _assign_banks(
    stage_eval: StageEval,
    m: int,
    eset: EffectiveSet,
    cfg: HMOOCConfig,
    k_obj: int,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Lines 4/7: evaluate members against their rep's optimal θp sets.

    One stage_eval per subQ covering every (member, bank slot) pair at once.
    """
    reqs = _assign_requests(m, eset, cfg)
    Fs = [stage_eval(i, req[0].Tc, req[0].Tps)
          for i, req in enumerate(reqs) if req is not None]
    return _assign_scatter(reqs, Fs, eset.Uc.shape[0], cfg, k_obj)


# ---------------------------------------------------------------------------
# DAG aggregation (paper §5.1.2, Appendix B)
# ---------------------------------------------------------------------------

def minkowski_merge_2d(F1: np.ndarray, S1: np.ndarray,
                       F2: np.ndarray, S2: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Pf(Pf(F)⊕Pf(G)) — enumerate sums, keep non-dominated (Alg. 3).

    S1/S2 are (n, m) per-subQ pool-index selections (−1 = unset); merged
    entries take whichever side set each subQ.
    """
    n1, n2 = F1.shape[0], F2.shape[0]
    F = (F1[:, None, :] + F2[None, :, :]).reshape(n1 * n2, -1)
    mask = pareto_mask_fast(F)
    keep = np.nonzero(mask)[0]
    i1, i2 = keep // n2, keep % n2
    sel = np.where(S1[i1] >= 0, S1[i1], S2[i2])
    return F[keep], sel


def _hmooc1_fixed_c(Fb: np.ndarray, Ib: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact divide-and-conquer aggregation under one θc (Alg. 2).

    Returns (front (q, k), sel (q, m)) with ``sel[:, i]`` the pool index
    chosen for subQ i.
    """
    m = Fb.shape[0]
    nodes = []
    for i in range(m):
        valid = np.isfinite(Fb[i]).all(-1)
        # Only local Pareto points can contribute (Prop. 5.1).
        valid &= pareto_mask_np(Fb[i], valid)
        idx = np.nonzero(valid)[0]
        if idx.size == 0:
            return np.zeros((0, Fb.shape[-1])), np.zeros((0, m), int)
        F = Fb[i][idx]
        sel = np.full((idx.size, m), -1, int)
        sel[:, i] = Ib[i][idx]
        nodes.append((F, sel))
    while len(nodes) > 1:
        nxt = []
        for a in range(0, len(nodes) - 1, 2):
            F, S = minkowski_merge_2d(nodes[a][0], nodes[a][1],
                                      nodes[a + 1][0], nodes[a + 1][1])
            nxt.append((F, S))
        if len(nodes) % 2:
            nxt.append(nodes[-1])
        nodes = nxt
    return nodes[0]


def _ws_pick(Fn: np.ndarray, W: np.ndarray) -> np.ndarray:
    """argmin_b  W[w] · Fn[c, i, b]  →  (nw, N, m) int.

    Routes through the ws_reduce Pallas kernel (one MXU matmul per bank)
    above the score-volume threshold; otherwise a float64 numpy einsum that
    reproduces the reference arithmetic bit-for-bit.

    Routing is tie-tolerant, like ``pareto_mask_fast``: when any objective
    column of ``Fn`` holds values that are distinct in float64 but collide
    after the kernel's float32 cast, the weighted argmin itself could flip
    under the cast, so such inputs take the float64 einsum regardless of
    volume.  (Conservative input-level check — it catches the cast-
    collision class; sums that tie only after f32 accumulation remain the
    kernel regime's documented f32 semantics.)
    """
    N, m, B, k = Fn.shape
    nw = W.shape[0]
    if N * m * B * nw >= _ws_min_scores() \
            and not _f32_tie_hazard(Fn.reshape(-1, k)):
        from ...kernels.ws_reduce import ws_reduce  # lazy: optional layer
        _, idx = ws_reduce(Fn.reshape(N * m, B, k), W)   # (nw, N*m)
        return np.asarray(idx, int).reshape(nw, N, m)
    scores = np.einsum("wk,cibk->wcib", W, Fn)           # (nw, N, m, B)
    return np.argmin(scores, axis=-1)


def _ws_weights(n_weights: int) -> np.ndarray:
    ws = np.linspace(0.0, 1.0, n_weights)
    return np.stack([ws, 1.0 - ws], axis=1)              # (nw, 2)


def _hmooc2_normalize(F_bank: np.ndarray) -> np.ndarray:
    # Normalize per OBJECTIVE over each candidate's whole bank (one affine
    # transform shared by every subQ).  The paper's Alg. 4 normalizes per
    # subQ, but per-subQ scales give each subQ different effective weights
    # and void Lemma 1's guarantee that each WS pick is query-level Pareto
    # optimal (hypothesis-tested in tests/test_hmooc.py); a shared affine
    # transform commutes with the sum aggregator and preserves the proof.
    finite = np.isfinite(F_bank)
    lo = np.min(np.where(finite, F_bank, np.inf), axis=(1, 2), keepdims=True)
    hi = np.max(np.where(finite, F_bank, -np.inf), axis=(1, 2), keepdims=True)
    span = np.where(hi > lo, hi - lo, 1.0)
    with np.errstate(invalid="ignore"):
        Fn = (F_bank - lo) / span
    return np.where(finite, Fn, 1e18)


def _hmooc2_all(F_bank: np.ndarray, idx_bank: np.ndarray, n_weights: int
                ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """WS-over-functions aggregation (Alg. 4), batched over θc candidates.

    Returns per-candidate (front (q, k), sel (q, m)) pairs.
    """
    N, m, B, k = F_bank.shape
    assert k == 2
    W = _ws_weights(n_weights)
    Fn = _hmooc2_normalize(F_bank)
    j = _ws_pick(Fn, W)                                  # (nw, N, m)
    jj = np.transpose(j, (1, 0, 2))                      # (N, nw, m)
    cc = np.arange(N)[:, None, None]
    ii = np.arange(m)[None, None, :]
    G = F_bank[cc, ii, jj]                               # (N, nw, m, k)
    S = idx_bank[cc, ii, jj]                             # (N, nw, m)
    ok = np.isfinite(G).all(axis=(2, 3))                 # (N, nw)
    P_all = G.sum(axis=2)                                # (N, nw, k)
    out: List[Tuple[np.ndarray, np.ndarray]] = []
    for c in range(N):
        rows = np.nonzero(ok[c])[0]
        if rows.size == 0:
            out.append((np.zeros((0, k)), np.zeros((0, m), int)))
            continue
        P = P_all[c, rows]
        mask = pareto_mask_fast(P)
        keep = np.nonzero(mask)[0]
        out.append((P[keep], S[c, rows][keep]))
    return out


def _hmooc2_fixed_c(Fb: np.ndarray, Ib: np.ndarray, n_weights: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """WS-over-functions aggregation under one θc (Alg. 4)."""
    return _hmooc2_all(Fb[None], Ib[None], n_weights)[0]


def _hmooc2_all_fused(Uc: np.ndarray, pool: np.ndarray, F_bank: np.ndarray,
                      idx_bank: np.ndarray, n_weights: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Kernel-regime HMOOC2: the whole aggregation in two device dispatches.

    One ``ws_reduce`` pass picks for every candidate and one global Pareto
    filter runs across all of them (``repro.kernels.fused_solve``), instead
    of bouncing intermediate banks between host and device per candidate;
    the objective sums and per-candidate masks stay float64 on the host.
    Returns the already-globally-filtered (front, theta_c, theta_ps) in the
    same row order the per-candidate numpy route produces (candidate-major,
    weight ascending), with its same f32 score/compare semantics.
    """
    from ...kernels.fused_solve import fused_ws_front  # lazy: optional layer
    N, m, B, k = F_bank.shape
    assert k == 2
    W = _ws_weights(n_weights)
    Fn = _hmooc2_normalize(F_bank)
    jj, P_all, keep = fused_ws_front(Fn, F_bank, W)
    cc = np.arange(N)[:, None, None]
    ii = np.arange(m)[None, None, :]
    S = idx_bank[cc, ii, jj]                             # (N, nw, m)
    keep_c, keep_w = np.nonzero(keep)
    theta_ps = pool[np.maximum(S[keep_c, keep_w], 0)]    # (q, m, d_ps)
    return P_all[keep_c, keep_w], Uc[keep_c], theta_ps


def _hmooc3_extremes(F_bank: np.ndarray, idx_bank: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Extreme points per θc (Prop. 5.2/5.3), fully vectorized.

    Returns (E, J): E (N, k, k) extreme objective vectors, J (N, k, m)
    per-subQ bank choices; E[c, v] is the query-level point minimizing
    objective v under θc candidate c.
    """
    N, m, B, k = F_bank.shape
    E = np.full((N, k, k), np.inf)
    J = np.full((N, k, m), -1, int)
    for v in range(k):
        j = np.argmin(np.where(np.isfinite(F_bank[..., v]),
                               F_bank[..., v], np.inf), axis=2)  # (N, m)
        gather = np.take_along_axis(
            F_bank, j[:, :, None, None].repeat(k, -1), axis=2)[:, :, 0, :]
        E[:, v, :] = gather.sum(1)
        J[:, v, :] = j
    return E, J


def dag_aggregate(
    Uc: np.ndarray,
    pool: np.ndarray,
    F_bank: np.ndarray,
    idx_bank: np.ndarray,
    method: str,
    *,
    n_ws_weights: int = 11,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Recover query-level Pareto solutions from per-subQ banks.

    Returns (front (q, k), theta_c (q, d_c), theta_ps (q, m, d_ps)).
    """
    N, m, B, k = F_bank.shape
    d_ps = pool.shape[1]

    if method == "hmooc3":
        E, J = _hmooc3_extremes(F_bank, idx_bank)
        pts = E.reshape(N * k, k)
        finite = np.isfinite(pts).all(-1)
        mask = pareto_mask_fast(pts) & finite
        keep = np.nonzero(mask)[0]
        front = pts[keep]
        theta_c = Uc[keep // k]
        c, v = keep // k, keep % k
        sel = np.take_along_axis(idx_bank[c], J[c, v][:, :, None],
                                 axis=2)[:, :, 0]          # (q, m)
        theta_ps = pool[np.maximum(sel, 0)]                # (q, m, d_ps)
        return front, theta_c, theta_ps

    fronts, tcs, sels = [], [], []
    if method == "hmooc2":
        # Tie-tolerant routing (same contract as `pareto_mask_fast`): the
        # fused kernel casts the bank to f32 for both the ws picks and the
        # global Pareto filter, so banks whose f64-distinct objective values
        # collide as f32 must take the per-candidate f64 numpy route even in
        # the kernel volume regime.  Input-level check on F_bank covers Fn
        # too (Fn is an affine renormalization of F_bank).
        if N * m * B * n_ws_weights >= _ws_min_scores() \
                and not _f32_tie_hazard(F_bank.reshape(-1, k)):
            return _hmooc2_all_fused(Uc, pool, F_bank, idx_bank,
                                     n_ws_weights)
        per_c: Sequence[Tuple[np.ndarray, np.ndarray]] = \
            _hmooc2_all(F_bank, idx_bank, n_ws_weights)
    elif method == "hmooc1":
        per_c = [_hmooc1_fixed_c(F_bank[c], idx_bank[c]) for c in range(N)]
    else:
        raise ValueError(method)
    for c, (F, S) in enumerate(per_c):
        if F.shape[0]:
            fronts.append(F)
            tcs.append(np.tile(Uc[c], (F.shape[0], 1)))
            sels.append(S)
    if not fronts:
        z = np.zeros((0, k))
        return z, np.zeros((0, Uc.shape[1])), np.zeros((0, m, d_ps))
    F = np.concatenate(fronts, 0)
    TC = np.concatenate(tcs, 0)
    SEL = np.concatenate(sels, 0)
    mask = pareto_mask_fast(F)
    keep = np.nonzero(mask)[0]
    theta_ps = pool[np.maximum(SEL[keep], 0)]   # (q, m, d_ps)
    return F[keep], TC[keep], theta_ps


# ---------------------------------------------------------------------------
# Full solve
# ---------------------------------------------------------------------------

def hmooc_solve(
    stage_eval: StageEval,
    m: int,
    d_c: int,
    d_ps: int,
    cfg: HMOOCConfig = HMOOCConfig(),
    *,
    snap_c=None,
    snap_ps=None,
    effective_set: Optional[EffectiveSet] = None,
) -> HMOOCResult:
    """Compile-time fine-grained MOO (subQ tuning + DAG aggregation).

    ``effective_set`` reuses Algorithm 1 artifacts from a previous solve:
    the candidate samples are always safe to share (they are
    query-independent for a fixed config); if ``opt_idx`` banks are present
    they are reused too, which skips the per-representative MOO entirely —
    exact when the query is identical to the one they were computed on.
    """
    t0 = time.perf_counter()
    reused_banks = False
    if effective_set is None:
        rng = np.random.default_rng(cfg.seed)
        eset = build_candidates(d_c, d_ps, cfg, snap_c=snap_c,
                                snap_ps=snap_ps, rng=rng)
    else:
        eset = effective_set
    n_evals = 0
    if eset.opt_idx is not None and len(eset.opt_idx[0]) == m:
        k_obj = eset.k_obj
        reused_banks = True
    else:
        opt_idx, k_obj, n_evals = _optimize_rep_banks(stage_eval, m, eset,
                                                      cfg)
        eset = dataclasses.replace(eset, opt_idx=opt_idx, k_obj=k_obj)
    F_bank, idx_bank, n2 = _assign_banks(stage_eval, m, eset, cfg, k_obj)
    n_evals += n2
    front, theta_c, theta_ps = dag_aggregate(
        eset.Uc, eset.pool, F_bank, idx_bank, cfg.dag_method,
        n_ws_weights=cfg.n_ws_weights)
    dt = time.perf_counter() - t0
    return HMOOCResult(front=front, theta_c=theta_c, theta_ps=theta_ps,
                       solve_time=dt, n_evals=n_evals,
                       extras={"n_theta_c": float(eset.Uc.shape[0]),
                               "reused_banks": float(reused_banks)},
                       effective_set=eset)


class HmoocPlan:
    """Externally-driven :func:`hmooc_solve`: one query's solve as a
    two-phase state machine whose stage evaluations are surfaced as request
    lists instead of executed inline.

    A batch driver (``repro.serve.service``) holds one plan per in-flight
    query, fuses every plan's pending requests into a single batched model
    dispatch per round, and feeds the results back — so a micro-batch of M
    queries costs two regressor calls total instead of 2·M·m.  The
    arithmetic is :func:`hmooc_solve`'s exactly: each phase's rows are built
    once, by the same :func:`_rep_bank_rows` / :func:`_assign_requests` the
    sequential solve calls, and the fed results go through the same
    :func:`_rep_banks` / :func:`_assign_scatter`.

    Protocol: while ``not plan.done``, call ``requests()`` (a list of
    ``(i, StageRows)`` stage requests), evaluate them externally, and pass
    the aligned objective arrays to ``feed()``.  ``banks_ready`` flips
    after the first phase, at which point ``eset`` carries the optimal-θp
    banks — a driver hands it to same-template plans to reuse, mirroring a
    sequential store→lookup between their solves.
    """

    def __init__(self, m: int, d_c: int, d_ps: int,
                 cfg: HMOOCConfig = HMOOCConfig(), *,
                 snap_c=None, snap_ps=None,
                 effective_set: Optional[EffectiveSet] = None):
        self._t0 = time.perf_counter()
        self.m, self.cfg = m, cfg
        self.n_evals = 0
        self.reused_banks = False
        self.result: Optional[HMOOCResult] = None
        if effective_set is None:
            rng = np.random.default_rng(cfg.seed)
            self.eset = build_candidates(d_c, d_ps, cfg, snap_c=snap_c,
                                         snap_ps=snap_ps, rng=rng)
        else:
            self.eset = effective_set
        if self.eset.opt_idx is not None and len(self.eset.opt_idx[0]) == m:
            self.k_obj = self.eset.k_obj
            self.reused_banks = True
            self._phase = "assign"
        else:
            self.k_obj = 2
            self._phase = "banks"
        self._reqs: Optional[List[Tuple[int, StageRows]]] = None
        self._assign: Optional[List[Optional[AssignRequest]]] = None

    @property
    def done(self) -> bool:
        return self._phase == "done"

    @property
    def banks_ready(self) -> bool:
        return self._phase in ("assign", "done")

    def requests(self) -> List[Tuple[int, StageRows]]:
        # Memoized per phase: the caller collects work from it once and
        # feed() reads the same rows (and the assign phase's chunks) back.
        if self._reqs is not None:
            return self._reqs
        if self._phase == "banks":
            rows = _rep_bank_rows(self.eset)
            self._reqs = [(i, rows) for i in range(self.m)]
        elif self._phase == "assign":
            self._assign = _assign_requests(self.m, self.eset, self.cfg)
            self._reqs = [(i, req[0]) for i, req in enumerate(self._assign)
                          if req is not None]
        else:
            raise RuntimeError("plan is already done")
        return self._reqs

    def feed(self, results: Sequence[np.ndarray]) -> None:
        """Advance one phase with the objective arrays for ``requests()``."""
        self.requests()
        self._reqs = None
        if self._phase == "banks":
            opt_idx, k_obj, n1 = _rep_banks(results, self.eset, self.cfg)
            self.eset = dataclasses.replace(self.eset, opt_idx=opt_idx,
                                            k_obj=k_obj)
            self.k_obj = k_obj
            self.n_evals += n1
            self._phase = "assign"
            return
        F_bank, idx_bank, n2 = _assign_scatter(
            self._assign, results, self.eset.Uc.shape[0], self.cfg,
            self.k_obj)
        self._assign = None
        self.n_evals += n2
        front, theta_c, theta_ps = dag_aggregate(
            self.eset.Uc, self.eset.pool, F_bank, idx_bank,
            self.cfg.dag_method, n_ws_weights=self.cfg.n_ws_weights)
        self.result = HMOOCResult(
            front=front, theta_c=theta_c, theta_ps=theta_ps,
            solve_time=time.perf_counter() - self._t0, n_evals=self.n_evals,
            extras={"n_theta_c": float(self.eset.Uc.shape[0]),
                    "reused_banks": float(self.reused_banks)},
            effective_set=self.eset)
        self._phase = "done"
