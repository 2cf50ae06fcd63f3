"""Pareto-set primitives used throughout HMOOC.

All objective arrays are *minimization* problems of shape ``(n, k)``.
Padded / invalid entries are handled through explicit validity masks so the
solver can run with fixed shapes under ``jax.jit``.

Three implementations of dominance filtering are provided:

* :func:`pareto_mask` — chunked O(n^2 k) jnp implementation, O(n * chunk)
  memory, jit/vmap friendly.  The default inside jitted solver code.
* :func:`pareto_mask_np` — plain numpy, used host-side for small dynamic sets.
* ``repro.kernels.pareto_filter`` — Pallas TPU kernel with the same semantics
  (imported lazily in :func:`pareto_mask_fast` to avoid circular imports).

Also includes Kung's O(n log n) algorithm for k=2 (host-side oracle) and
2-objective hypervolume.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "pareto_mask",
    "pareto_mask_np",
    "pareto_mask_fast",
    "kung_2d_np",
    "filter_dominated_np",
    "compact_bank",
    "hypervolume_2d",
]

def backend() -> str:
    # Resolved lazily — jax.default_backend() initializes the XLA runtime,
    # which must not happen as an import side effect — and per *call*:
    # caching the answer (the pre-PR-7 lru_cache) froze routing at the
    # first use, so a backend initialized or overridden later was ignored.
    return jax.default_backend()


# Row count above which dominance masks route to the Pallas kernel.  On TPU
# the kernel wins early; on CPU hosts the interpret-mode kernel never beats
# the O(n log n) numpy sweep, so the default keeps the numpy path (and its
# float64 determinism) unless explicitly overridden.  None = resolve from
# the env var / backend per call (tests monkeypatch this directly).
_KERNEL_MIN_N = None


def _default_kernel_min_n() -> int:
    # Read per call, never cached: REPRO_PARETO_KERNEL_MIN_N flipped after
    # import (tests, operators re-tuning a live process) must take effect.
    return int(os.environ.get(
        "REPRO_PARETO_KERNEL_MIN_N",
        "512" if backend() == "tpu" else str(1 << 30)))


# ---------------------------------------------------------------------------
# jnp implementations
# ---------------------------------------------------------------------------

def _dominates_block(Fj: jnp.ndarray, Fi: jnp.ndarray, vj: jnp.ndarray) -> jnp.ndarray:
    """dom[i] |= exists j in block with F[j] <= F[i] (all) and < in one.

    Fj: (c, k) candidate dominators, Fi: (n, k), vj: (c,) validity of block.
    Returns (n,) bool.
    """
    le = (Fj[:, None, :] <= Fi[None, :, :]).all(-1)  # (c, n)
    lt = (Fj[:, None, :] < Fi[None, :, :]).any(-1)   # (c, n)
    return ((le & lt) & vj[:, None]).any(0)


@functools.partial(jax.jit, static_argnames=("chunk",))
def pareto_mask(
    F: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    chunk: int = 256,
) -> jnp.ndarray:
    """Boolean mask of Pareto-optimal (non-dominated) rows of ``F``.

    Args:
      F: (n, k) objective values, minimization.  ``inf`` rows are never optimal.
      valid: optional (n,) bool; invalid rows are neither optimal nor dominate.
      chunk: j-block size; memory is O(n * chunk).
    """
    n, _ = F.shape
    if valid is None:
        valid = jnp.isfinite(F).all(-1)
    else:
        valid = valid & jnp.isfinite(F).all(-1)
    # Pad to a multiple of chunk.
    pad = (-n) % chunk
    Fp = jnp.pad(F, ((0, pad), (0, 0)), constant_values=jnp.inf)
    vp = jnp.pad(valid, (0, pad), constant_values=False)
    nblocks = Fp.shape[0] // chunk

    def body(b, dom):
        Fj = jax.lax.dynamic_slice_in_dim(Fp, b * chunk, chunk, 0)
        vj = jax.lax.dynamic_slice_in_dim(vp, b * chunk, chunk, 0)
        return dom | _dominates_block(Fj, F, vj)

    dom = jax.lax.fori_loop(0, nblocks, body, jnp.zeros((n,), bool))
    return valid & ~dom


def compact_bank(
    F: jnp.ndarray,
    mask: jnp.ndarray,
    p: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Gather up to ``p`` masked rows of ``F`` to the front, padding with +inf.

    Returns (Fout (p, k), valid (p,), idx (p,)) where idx are source row
    indices (arbitrary for padded slots).  Jit-safe (fixed output shape).
    If more than ``p`` rows are selected the surplus is dropped in index order.
    """
    n, k = F.shape
    order = jnp.argsort(~mask, stable=True)  # non-dominated first
    idx = order[:p]
    take_valid = mask[idx]
    Fout = jnp.where(take_valid[:, None], F[idx], jnp.inf)
    return Fout, take_valid, idx


# ---------------------------------------------------------------------------
# numpy implementations (host-side, dynamic shapes)
# ---------------------------------------------------------------------------

def pareto_mask_np(F: np.ndarray, valid: Optional[np.ndarray] = None) -> np.ndarray:
    """Numpy dominance mask; O(n log n) sweep for k=2, O(n² k) otherwise."""
    F = np.asarray(F, dtype=np.float64)
    n = F.shape[0]
    if valid is None:
        valid = np.isfinite(F).all(-1)
    else:
        valid = np.asarray(valid, bool) & np.isfinite(F).all(-1)
    if n == 0:
        return valid
    if F.shape[1] == 2 and n > 64:
        return _pareto_mask_2d_np(F, valid)
    le = (F[:, None, :] <= F[None, :, :]).all(-1)
    lt = (F[:, None, :] < F[None, :, :]).any(-1)
    dom = ((le & lt) & valid[:, None]).any(0)
    return valid & ~dom


def _pareto_mask_2d_np(F: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """O(n log n) two-objective dominance mask (duplicate optima survive)."""
    n = F.shape[0]
    mask = np.zeros(n, bool)
    idx = np.nonzero(valid)[0]
    if idx.size == 0:
        return mask
    order = idx[np.lexsort((F[idx, 1], F[idx, 0]))]
    f0 = F[order, 0]
    f1 = F[order, 1]
    # Group by distinct f0; group minimum of f1 (within-group dominance).
    new_grp = np.empty(order.size, bool)
    new_grp[0] = True
    new_grp[1:] = f0[1:] != f0[:-1]
    grp = np.cumsum(new_grp) - 1
    n_grp = grp[-1] + 1
    grp_min = np.full(n_grp, np.inf)
    np.minimum.at(grp_min, grp, f1)
    # Running strict-prefix min of f1 over earlier (strictly smaller f0) groups.
    prev_best = np.empty(n_grp)
    prev_best[0] = np.inf
    if n_grp > 1:
        prev_best[1:] = np.minimum.accumulate(grp_min)[:-1]
    keep = (f1 == grp_min[grp]) & (f1 < prev_best[grp])
    mask[order[keep]] = True
    return mask


def pareto_mask_fast(F: np.ndarray,
                     valid: Optional[np.ndarray] = None) -> np.ndarray:
    """Dominance mask dispatcher: Pallas kernel for large n, numpy below.

    Same semantics as :func:`pareto_mask_np`.  Rows are bucket-padded to a
    power of two before hitting the jitted kernel so the compile cache sees
    only O(log n) distinct shapes across a serving session.  The kernel
    compares in float32; the numpy fallback keeps float64 — callers that
    need bit-stable fronts on CPU get them by default (see ``_KERNEL_MIN_N``).

    Routing is tie-tolerant: when any objective column holds values that
    are distinct in float64 but collide after the kernel's float32 cast,
    the dominance relation itself would change under the cast (a strictly
    dominated point can tie its dominator and survive), so such inputs
    take the float64 numpy path regardless of size.  This keeps the mask a
    pure function of the input values rather than of the backend the batch
    happened to route to.
    """
    F = np.asarray(F, np.float64)
    n = F.shape[0]
    thr = _KERNEL_MIN_N if _KERNEL_MIN_N is not None \
        else _default_kernel_min_n()
    if n < thr or n == 0:
        return pareto_mask_np(F, valid)
    if _f32_tie_hazard(F):
        return pareto_mask_np(F, valid)
    return _pareto_mask_kernel(F, valid)


def _f32_tie_hazard(F: np.ndarray) -> bool:
    """True if float64-distinct values in some column tie as float32."""
    for j in range(F.shape[1]):
        col = F[:, j]
        u = np.unique(col[np.isfinite(col)])
        if np.unique(u.astype(np.float32)).size < u.size:
            return True
    return False


def _pareto_mask_kernel(F: np.ndarray,
                        valid: Optional[np.ndarray] = None) -> np.ndarray:
    from ...kernels.pareto_filter import pareto_filter  # lazy: optional layer
    n, k = F.shape
    if valid is None:
        v = np.isfinite(F).all(-1)
    else:
        v = np.asarray(valid, bool) & np.isfinite(F).all(-1)
    bucket = max(128, 1 << int(np.ceil(np.log2(max(n, 2)))))
    Fp = np.full((bucket, k), np.inf)
    Fp[:n] = np.where(np.isfinite(F), F, np.inf)
    vp = np.zeros(bucket, bool)
    vp[:n] = v
    mask = np.asarray(pareto_filter(jnp.asarray(Fp, jnp.float32),
                                    jnp.asarray(vp)))
    return mask[:n]


def kung_2d_np(F: np.ndarray) -> np.ndarray:
    """Kung's O(n log n) Pareto mask for k=2 minimization (numpy, oracle)."""
    F = np.asarray(F, dtype=np.float64)
    n = F.shape[0]
    mask = np.zeros(n, bool)
    finite = np.isfinite(F).all(-1)
    idx = np.nonzero(finite)[0]
    if idx.size == 0:
        return mask
    # sort by (f0 asc, f1 asc); sweep keeping running min of f1
    order = idx[np.lexsort((F[idx, 1], F[idx, 0]))]
    best = np.inf
    for i in order:
        if F[i, 1] < best:
            mask[i] = True
            best = F[i, 1]
    # Equal points: the sweep keeps the first of duplicates only, which is a
    # valid Pareto subset; mark exact duplicates of kept points as optimal too.
    kept = F[mask]
    for i in idx:
        if not mask[i] and kept.size and (kept == F[i]).all(-1).any():
            mask[i] = True
    return mask


def filter_dominated_np(
    F: np.ndarray, payload: Optional[np.ndarray] = None
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Return the non-dominated subset of F (and aligned payload rows)."""
    m = pareto_mask_np(F)
    if payload is None:
        return F[m], None
    return F[m], payload[m]


# ---------------------------------------------------------------------------
# Hypervolume (benchmark metric; paper's HV)
# ---------------------------------------------------------------------------

def hypervolume_2d(F: np.ndarray, ref: np.ndarray) -> float:
    """Exact 2-objective hypervolume dominated by F w.r.t. reference point.

    Points not dominating ``ref`` contribute nothing.
    """
    F = np.asarray(F, np.float64)
    ref = np.asarray(ref, np.float64)
    if F.size == 0:
        return 0.0
    F = F[np.isfinite(F).all(-1)]
    F = F[(F < ref).all(-1)]
    if F.shape[0] == 0:
        return 0.0
    m = pareto_mask_np(F)
    P = np.unique(F[m], axis=0)  # sorted by f0 asc then f1 asc
    hv = 0.0
    prev_f1 = ref[1]
    for f0, f1 in P:
        if f1 < prev_f1:
            hv += (ref[0] - f0) * (prev_f1 - f1)
            prev_f1 = f1
    return float(hv)
