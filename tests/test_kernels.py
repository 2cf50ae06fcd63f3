"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import ml_dtypes
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.ops import attention_ref, flash_attention
from repro.kernels.pareto_filter.ops import pareto_filter, pareto_mask_ref
from repro.kernels.ws_reduce.ops import ws_reduce, ws_reduce_ref


@pytest.mark.parametrize("n,k", [(4, 2), (128, 2), (200, 3), (513, 4),
                                 (64, 8), (1, 2)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pareto_filter(n, k, dtype):
    rng = np.random.default_rng(n * 10 + k)
    F = jnp.asarray(rng.integers(0, 9, size=(n, k)).astype(dtype))
    valid = jnp.asarray(rng.random(n) > 0.15)
    got = pareto_filter(F, valid)
    ref = pareto_mask_ref(F, valid)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("m,B,k,nw", [(1, 8, 2, 3), (4, 130, 2, 11),
                                      (3, 48, 3, 33), (2, 256, 4, 128)])
def test_ws_reduce(m, B, k, nw):
    rng = np.random.default_rng(m * 100 + B)
    F = rng.random((m, B, k)).astype(np.float32)
    F[:, -2:] = np.inf                       # padded bank slots
    W = rng.random((nw, k)).astype(np.float32)
    v, i = ws_reduce(jnp.asarray(F), jnp.asarray(W))
    vr, ir = ws_reduce_ref(jnp.nan_to_num(jnp.asarray(F), posinf=1e30),
                           jnp.asarray(W))
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,causal",
    [(1, 4, 4, 128, 128, 64, True),
     (2, 8, 2, 256, 256, 64, True),      # GQA
     (1, 4, 1, 100, 100, 128, True),     # ragged + MQA
     (1, 4, 2, 1, 300, 64, False),       # decode
     (1, 8, 4, 96, 480, 64, True),       # continuation chunk
     (2, 2, 2, 64, 64, 128, False)])
def test_flash_attention_f32(B, Hq, Hkv, Sq, Skv, D, causal):
    rng = np.random.default_rng(Sq + Skv)
    q = jnp.asarray(rng.normal(size=(B, Hq, Sq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Skv, D)), jnp.float32)
    got = flash_attention(q, k, v, causal=causal)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(0)
    shape_q = (1, 4, 128, 128)
    q = jnp.asarray(rng.normal(size=shape_q).astype(ml_dtypes.bfloat16))
    k = jnp.asarray(rng.normal(size=shape_q).astype(ml_dtypes.bfloat16))
    v = jnp.asarray(rng.normal(size=shape_q).astype(ml_dtypes.bfloat16))
    got = flash_attention(q, k, v, causal=True)
    ref = attention_ref(q, k, v, causal=True)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_backend_detection_resolves_at_call_time(monkeypatch):
    """The interpret default must track the *current* backend, not the one
    active when the ops module was imported (backends can be initialized or
    overridden after import)."""
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.pareto_filter import ops as pf_ops
    from repro.kernels.ws_reduce import ops as ws_ops

    host = jax.default_backend()
    for ops in (fa_ops, pf_ops, ws_ops):
        assert ops._default_interpret() is (host != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for ops in (fa_ops, pf_ops, ws_ops):
        assert ops._default_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    for ops in (fa_ops, pf_ops, ws_ops):
        assert ops._default_interpret() is True


def test_fused_ws_front_composed_solve():
    """fused_solve: ws_reduce picks + objective sums + local/global Pareto
    composed under one jit, checked against a hand-computed case."""
    from repro.kernels.fused_solve import SEEN_BUCKETS, fused_ws_front

    N, m, B, k, nw = 3, 2, 2, 2, 4
    rng = np.random.default_rng(0)
    Fb = rng.random((N, m, B, k))
    Fb[:, :, 0] = Fb[:, :, 1] - 1.0   # bank 0 strictly dominates bank 1
    W = np.stack([np.linspace(0.1, 0.9, nw),
                  1.0 - np.linspace(0.1, 0.9, nw)], -1)
    Fn = Fb.astype(np.float32).astype(np.float64)
    jj, P_all, keep = fused_ws_front(Fn.astype(np.float32), Fb, W)
    assert jj.shape == (N, nw, m) and P_all.shape == (N, nw, k)
    assert (jj == 0).all()            # every weight picks the dominant bank
    np.testing.assert_allclose(P_all, np.broadcast_to(
        Fb[:, :, 0].sum(axis=1)[:, None, :], (N, nw, k)), rtol=1e-12)
    # Every weight row of a candidate lands on the same objective sum, so a
    # candidate either survives the global filter with all rows (duplicate
    # optima survive, matching the numpy dominance semantics) or with none.
    from repro.core.moo.pareto import pareto_mask_np
    cand_mask = pareto_mask_np(Fb[:, :, 0].sum(axis=1))
    np.testing.assert_array_equal(keep.any(axis=1), cand_mask)
    assert (keep.sum(axis=1)[cand_mask] == nw).all()
    assert any(b[0] >= N and b[1] >= m for b in SEEN_BUCKETS)


@pytest.mark.parametrize("N,m,B,k,nw", [(1, 1, 2, 2, 3), (3, 2, 8, 2, 11),
                                        (7, 3, 16, 2, 6), (33, 5, 4, 2, 4)])
def test_fused_ws_front_vs_ref(N, m, B, k, nw):
    """Parity: the fused jit against the pure-numpy oracle, including banks
    with padded (+inf) slots."""
    from repro.kernels.fused_solve import fused_ws_front, fused_ws_front_ref

    rng = np.random.default_rng(N * 1000 + m * 10 + B)
    Fb = rng.random((N, m, B, k))
    if B > 2:
        Fb[:, :, -1] = np.inf         # padded bank slot everywhere
        Fb[0, 0, -2] = np.inf
    W = np.stack([np.linspace(0.05, 0.95, nw),
                  1.0 - np.linspace(0.05, 0.95, nw)], -1)
    lo = np.nanmin(np.where(np.isfinite(Fb), Fb, np.nan), axis=(1, 2),
                   keepdims=True)
    hi = np.nanmax(np.where(np.isfinite(Fb), Fb, np.nan), axis=(1, 2),
                   keepdims=True)
    Fn = np.where(np.isfinite(Fb), (Fb - lo) / np.where(hi > lo, hi - lo,
                                                        1.0), 1e18)
    jj, P_all, keep = fused_ws_front(Fn.astype(np.float32), Fb, W)
    jr, Pr, kr = fused_ws_front_ref(Fn.astype(np.float32), Fb, W)
    np.testing.assert_array_equal(jj, jr)
    np.testing.assert_allclose(P_all, Pr, rtol=1e-12)
    np.testing.assert_array_equal(keep, kr)


def test_fused_ws_front_padding_invalid():
    """Padded candidates/subQs and non-finite banks never reach the front."""
    from repro.kernels.fused_solve import fused_ws_front

    rng = np.random.default_rng(1)
    N, m, B, k, nw = 5, 3, 4, 2, 6
    Fb = rng.random((N, m, B, k))
    Fb[2, 1] = np.inf                 # a subQ with an empty bank
    W = np.stack([np.linspace(0.05, 0.95, nw),
                  1.0 - np.linspace(0.05, 0.95, nw)], -1)
    Fn = Fb.astype(np.float32)
    jj, P_all, keep = fused_ws_front(Fn, Fb, W)
    assert not keep[2].any()          # invalid candidate filtered
    assert keep.any()                 # but the rest produce a front
    assert np.isfinite(P_all[keep]).all()


def _f32_running_sum(x: np.ndarray) -> np.float32:
    s = np.float32(x[0])
    for t in x[1:]:
        s = np.float32(s + np.float32(t))
    return s


def test_fused_route_keeps_front_when_f32_sums_tie():
    """Two weight picks whose objective sums differ in float64 (and after a
    float32 cast) but tie when added up in float32: the fused kernel route
    must keep both, like the float64 numpy route."""
    from repro.core.moo.hmooc import _hmooc2_all_fused, dag_aggregate
    from repro.kernels.fused_solve import fused_ws_front, fused_ws_front_ref

    m, one, ulp = 48, np.float32(1.0), np.float32(2.0 ** -23)
    rng = np.random.default_rng(0)
    while True:   # per-subQ latencies, exact in float32, b < a everywhere
        a = one + rng.integers(0, 8, m).astype(np.float32) * ulp
        b = a - rng.integers(1, 3, m).astype(np.float32) * ulp
        sa, sb = a.astype(np.float64).sum(), b.astype(np.float64).sum()
        if _f32_running_sum(a) == _f32_running_sum(b) and \
                np.float32(sb) < np.float32(sa):
            break
    # Bank 0: (a, cost 1); bank 1: (b, cost 2).  Weight (0, 1) picks bank 0
    # everywhere, weight (1, 0) bank 1: two mutually non-dominated points.
    F_bank = np.empty((1, m, 2, 2))
    F_bank[0, :, 0] = np.stack([a, np.ones(m)], -1)
    F_bank[0, :, 1] = np.stack([b, np.full(m, 2.0)], -1)
    idx_bank = np.tile(np.arange(2), (1, m, 1))
    Uc = np.array([[0.5, 0.25]])
    pool = np.array([[0.0], [1.0]])

    front, tc, tps = dag_aggregate(Uc, pool, F_bank, idx_bank, "hmooc2",
                                   n_ws_weights=2)
    assert front.shape[0] == 2        # the numpy route keeps both picks
    got = _hmooc2_all_fused(Uc, pool, F_bank, idx_bank, 2)
    for x, y in zip(got, (front, tc, tps)):
        np.testing.assert_array_equal(x, y)

    W = np.array([[0.0, 1.0], [1.0, 0.0]])
    Fn = ((F_bank - F_bank.min((1, 2), keepdims=True))
          / np.ptp(F_bank, axis=(1, 2), keepdims=True)).astype(np.float32)
    jj, P_all, keep = fused_ws_front(Fn, F_bank, W)
    jr, Pr, kr = fused_ws_front_ref(Fn, F_bank, W)
    assert keep.all()
    np.testing.assert_array_equal(jj, jr)
    np.testing.assert_array_equal(P_all, Pr)
    np.testing.assert_array_equal(keep, kr)
