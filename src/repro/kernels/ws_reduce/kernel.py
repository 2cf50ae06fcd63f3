"""Pallas TPU kernel: weighted-sum reduction over per-subQ solution banks.

HMOOC2's hot loop: for every weight vector w and every subQ bank F_m, find
argmin_j  w · F_m[j].  One grid step processes ``SUBQ_BLOCK`` subQs: the
(NW, KPAD) weight tile and the (SUBQ_BLOCK, B, KPAD) bank tile are
VMEM-resident, and each subQ's score matrix F_m @ Wᵀ is one MXU matmul —
NW and B are padded to 128 so the matmul runs at full systolic
utilization; the argmin is a reduction over the bank (sublane) axis, which
leaves each subQ's (NW,) result on the lane axis, one row of the output
block.

Blocks: the TPU compiler takes a block whose last two dims are multiples of
(8, 128) or equal to the array's.  The (SUBQ_BLOCK, NWp) output block meets
that for any subQ count because the subQ axis is padded to SUBQ_BLOCK; a
block of one subQ row over an (m, NWp) output does not (refused for m > 1).

The matmul runs at ``Precision.HIGHEST``: the default TPU precision rounds
the float32 operands to bfloat16, which merges scores that differ in
float32 and flips picks the callers' float32 tie guards
(``_f32_tie_hazard``) assume cannot flip.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ws_reduce_pallas", "KPAD", "SUBQ_BLOCK"]

KPAD = 8
SUBQ_BLOCK = 8


def _kernel(W_ref, F_ref, val_ref, idx_ref):
    W = W_ref[...]                                  # (NWp, KPAD)
    for s in range(SUBQ_BLOCK):
        F = F_ref[s]                                # (Bp, KPAD)
        scores = jax.lax.dot_general(
            F, W, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)     # (Bp, NWp) MXU
        best = jnp.min(scores, axis=0, keepdims=True)          # (1, NWp)
        row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        # First index attaining the minimum (np.argmin's tie rule).
        first = jnp.min(jnp.where(scores == best, row, scores.shape[0]),
                        axis=0, keepdims=True)
        val_ref[pl.ds(s, 1), :] = best
        idx_ref[pl.ds(s, 1), :] = first


@functools.partial(jax.jit, static_argnames=("interpret",))
def ws_reduce_pallas(F: jnp.ndarray, W: jnp.ndarray,
                     *, interpret: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(m, B, k) banks × (nw, k) weights → (vals, idx) each (nw, m).

    Banks are padded B→multiple of 128 with +1e30 sentinels (never argmin
    unless the bank is empty), k→KPAD with zeros (weights padded with
    zeros, so extra columns never contribute) and m→multiple of SUBQ_BLOCK
    with zero banks (sliced off).
    """
    m, B, k = F.shape
    nw = W.shape[0]
    Bp = max(128, ((B + 127) // 128) * 128)
    NWp = max(128, ((nw + 127) // 128) * 128)
    mp = -(-m // SUBQ_BLOCK) * SUBQ_BLOCK
    F32 = jnp.nan_to_num(F.astype(jnp.float32), posinf=1e30)
    Fp = jnp.pad(F32, ((0, mp - m), (0, Bp - B), (0, KPAD - k)),
                 constant_values=0.0)
    if Bp > B:
        Fp = Fp.at[:, B:, :k].set(1e30)
    Wp = jnp.pad(W.astype(jnp.float32), ((0, NWp - nw), (0, KPAD - k)),
                 constant_values=0.0)

    vals, idx = pl.pallas_call(
        _kernel,
        grid=(mp // SUBQ_BLOCK,),
        in_specs=[
            pl.BlockSpec((NWp, KPAD), lambda i: (0, 0)),
            pl.BlockSpec((SUBQ_BLOCK, Bp, KPAD), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((SUBQ_BLOCK, NWp), lambda i: (i, 0)),
            pl.BlockSpec((SUBQ_BLOCK, NWp), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, NWp), jnp.float32),
            jax.ShapeDtypeStruct((mp, NWp), jnp.int32),
        ],
        interpret=interpret,
    )(Wp, Fp)

    return vals[:m, :nw].T, idx[:m, :nw].T
