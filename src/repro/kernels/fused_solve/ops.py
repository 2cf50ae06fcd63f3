"""Fused HMOOC2 aggregation: ws_reduce picks + global Pareto filter.

The kernel-regime HMOOC2 path used to make a ``ws_reduce`` round-trip and
a ``pareto_filter`` round-trip per candidate.  :func:`fused_ws_front` makes
two device dispatches for the whole aggregation: one MXU weighted-sum
reduction over every (candidate, subQ) bank, then one global Pareto filter
across every (candidate, weight) point.  Between them the host gathers the
picked bank rows, adds up the objective sums and applies the per-candidate
dominance mask, all in float64.

Shape policy: the candidate axis N and the subQ axis m are padded to
power-of-two buckets (tracked in :data:`SEEN_BUCKETS`), so a serving session
compiles O(log N_max · log m_max) signatures however query shapes vary.
Padded candidates carry 1e18 scores and padded subQs all-zero scores; their
picks are sliced off before the host reads them.

Numerical semantics: the device holds float32 only (the TPU has no native
float64).  Weighted-sum scores compare in float32.  The objective sums and
the per-candidate mask are float64, as on the numpy route; the global
filter compares the float64 sums cast to float32, a cast that keeps their
order (it can only merge near-equal sums into ties).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..pareto_filter.kernel import pareto_filter_pallas
from ..ws_reduce.kernel import ws_reduce_pallas

__all__ = ["fused_ws_front", "SEEN_BUCKETS"]

# (N bucket, m bucket, B, k, nw) signatures dispatched so far — at most
# one per bucket, however the solved shapes vary.
SEEN_BUCKETS: set = set()


def _pow2(n: int, lo: int) -> int:
    return max(lo, 1 << (max(n, 1) - 1).bit_length())


def _local_mask(P: np.ndarray, v: np.ndarray) -> np.ndarray:
    """Non-dominated mask over each candidate's (nw, k) weight picks:
    (N, nw, k) sums + (N, nw) validity → (N, nw)."""
    le = (P[:, :, None, :] <= P[:, None, :, :]).all(-1)
    lt = (P[:, :, None, :] < P[:, None, :, :]).any(-1)
    dom = ((le & lt) & v[:, :, None]).any(1)
    return v & ~dom


def _fused_impl(Fn, W, *, interpret: bool):
    """(Np, mp, B, k) scores × (nw, k) weights → (Np, nw, mp) picks."""
    Np, mp, B, k = Fn.shape
    nw = W.shape[0]
    # One MXU pass over every (candidate, subQ) bank.
    _, idx = ws_reduce_pallas(Fn.reshape(Np * mp, B, k), W,
                              interpret=interpret)        # (nw, Np*mp)
    return idx.T.reshape(Np, mp, nw).transpose(0, 2, 1)


_fused = jax.jit(_fused_impl, static_argnames=("interpret",))


def fused_ws_front(Fn: np.ndarray, F_bank: np.ndarray, W: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(N, m, B, k) normalized scores + raw banks + (nw, k) weights →
    (jj (N, nw, m) picks, P_all (N, nw, k) objective sums, keep (N, nw)).

    ``keep`` composes validity, the per-candidate dominance mask over the
    weight picks, and the global Pareto filter across all candidates —
    ``P_all[keep]`` is the query-level front, already globally filtered.
    """
    N, m, B, k = F_bank.shape
    nw = W.shape[0]
    Np, mp = _pow2(N, 32), _pow2(m, 4)
    SEEN_BUCKETS.add((Np, mp, B, k, nw))
    interpret = jax.default_backend() != "tpu"
    Fnp = np.zeros((Np, mp, B, k), np.float32)
    Fnp[:N, :m] = Fn
    Fnp[N:] = 1e18
    jj = np.asarray(_fused(jnp.asarray(Fnp), jnp.asarray(W, jnp.float32),
                           interpret=interpret))[:N, :, :m]
    cc = np.arange(N)[:, None, None]
    ii = np.arange(m)[None, None, :]
    G = np.asarray(F_bank, np.float64)[cc, ii, jj]         # (N, nw, m, k)
    P_all = G.sum(axis=2)                                  # (N, nw, k)
    ok = np.isfinite(G).all(axis=(2, 3))                   # (N, nw)
    v = _local_mask(P_all, ok)
    # Global filter over a padded (Np * nw) row bucket; padded rows invalid.
    P32 = np.zeros((Np * nw, k), np.float32)
    P32[:N * nw] = np.where(v[..., None], P_all, 0.0).reshape(-1, k)
    vp = np.zeros(Np * nw, bool)
    vp[:N * nw] = v.reshape(-1)
    keep = np.asarray(pareto_filter_pallas(jnp.asarray(P32), jnp.asarray(vp),
                                           interpret=interpret))
    return jj, P_all, keep[:N * nw].reshape(N, nw)
