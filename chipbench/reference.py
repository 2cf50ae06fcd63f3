"""Plain reference of the served performance model and of its objectives.

Imports nothing of the program.  It holds copies of what a served answer
depends on, written out once here so that a change to the program cannot
move them:

* the subQ graph features (``repro.core.models.features``: operator
  one-hot, log cardinalities, hashed predicate tokens, Laplacian
  positional encodings, structure flags);
* the GTN embedder and the regressor head (``gtn.py``, ``nn.py``):
  pre-norm attention with per-head structure biases, GELU (tanh form)
  MLPs, masked mean pool;
* the unit <-> raw maps of the 19 Spark parameters (``spark_space.py``)
  and the alpha statistics and dollar cost of a stage (``trace.py``,
  ``objectives.py``).

``embed`` and ``head`` run in the arithmetic that ``arith`` gives: numpy
float64 (the reference the check compares with), ``jax.numpy`` float32 at
a matmul precision (the benchmark's training, at ``HIGHEST``), or float32
with split bfloat16 products (the precision controls).
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, Sequence, Tuple

import numpy as np

OP_TYPES = ["scan", "filter", "project", "join", "agg", "sort", "exchange",
            "limit", "expand", "window"]
PRED_DIM = 8
PE_DIM = 4
FEAT_DIM = len(OP_TYPES) + 2 + PRED_DIM
NOND_DIM = 12            # alpha (5) + beta (3) + gamma (4)
TARGET_EPS = 1e-3
_HASH_SEED = 1234

# (kind, lo, hi, log) of each parameter, in the program's order (paper
# Table 6): theta_c (8), theta_p (9), theta_s (2).
THETA_C = [("int", 1, 8, False), ("int", 1, 32, True), ("int", 2, 20, False),
           ("int", 8, 512, True), ("int", 8, 256, True),
           ("int", 50, 1000, False), ("bool", 0, 1, False),
           ("float", 0.4, 0.9, False)]
THETA_P = [("int", 8, 512, True), ("float", 0.0, 1.0, False),
           ("int", 0, 1024, False), ("int", 0, 1024, False),
           ("int", 8, 2048, True), ("int", 16, 1024, True),
           ("int", 2, 10, False), ("int", 16, 1024, True),
           ("int", 1, 64, True)]
THETA_S = [("float", 0.1, 0.9, False), ("int", 1, 64, True)]


# -- features ---------------------------------------------------------------

@functools.lru_cache(maxsize=65536)
def _token_vec(token: str) -> np.ndarray:
    seed = (zlib.crc32(token.encode()) ^ _HASH_SEED) % (2 ** 32)
    return np.random.default_rng(seed).normal(0, 1, PRED_DIM) / np.sqrt(PRED_DIM)


def _lap_pe(A: np.ndarray) -> np.ndarray:
    n = A.shape[0]
    und = ((A + A.T) > 0).astype(np.float64)
    deg = und.sum(1)
    dis = np.where(deg > 0, 1.0 / np.sqrt(np.maximum(deg, 1e-9)), 0.0)
    L = np.eye(n) - dis[:, None] * und * dis[None, :]
    vals, vecs = np.linalg.eigh(L)
    pe = vecs[:, np.argsort(vals)[1:PE_DIM + 1]] if n > 1 else np.zeros((n, 0))
    for j in range(pe.shape[1]):
        i = int(np.argmax(np.abs(pe[:, j])))
        if pe[i, j] < 0:
            pe[:, j] = -pe[:, j]
    out = np.zeros((n, PE_DIM), np.float32)
    out[:, :pe.shape[1]] = pe
    return out


def subq_graph(query, sq_id: int, *, use_est: bool, n_pad: int = 4
               ) -> Tuple[np.ndarray, ...]:
    """(X, pe, bias, mask) of one subQ's operator group, padded to n_pad."""
    ops = [query.ops[i] for i in query.subqs[sq_id].op_ids]
    local = {op.op_id: j for j, op in enumerate(ops)}
    n = len(ops)
    X = np.zeros((n, FEAT_DIM), np.float32)
    A = np.zeros((n, n), np.float32)
    for i, op in enumerate(ops):
        X[i, OP_TYPES.index(op.op_type)] = 1.0
        rows = op.est_rows if use_est else op.rows
        bys = op.est_bytes if use_est else op.bytes
        X[i, len(OP_TYPES)] = np.log1p(max(rows, 0.0)) / 25.0
        X[i, len(OP_TYPES) + 1] = np.log1p(max(bys, 0.0)) / 30.0
        if op.pred_tokens:
            X[i, len(OP_TYPES) + 2:] = np.mean(
                [_token_vec(t) for t in op.pred_tokens], axis=0)
        for c in op.children:
            if c in local:
                A[local[c], i] = 1.0
    Xp = np.zeros((n_pad, FEAT_DIM), np.float32)
    Xp[:n] = X
    pep = np.zeros((n_pad, PE_DIM), np.float32)
    pep[:n] = _lap_pe(A)
    bias = np.zeros((n_pad, n_pad, 3), np.float32)
    bias[:n, :n, 0] = A
    bias[:n, :n, 1] = A.T
    bias[range(n), range(n), 2] = 1.0
    mask = np.zeros((n_pad,), bool)
    mask[:n] = True
    return Xp, pep, bias, mask


def stack_graphs(graphs: Sequence[Tuple[np.ndarray, ...]]):
    return tuple(np.stack([g[i] for g in graphs]) for i in range(4))


def alpha_stats(rows: Sequence[float], bys: Sequence[float]) -> np.ndarray:
    return np.array([np.log1p(float(sum(rows))) / 20.0,
                     np.log1p(float(sum(bys))) / 25.0,
                     np.log1p(float(max(rows))) / 20.0,
                     np.log1p(float(max(bys))) / 25.0,
                     len(rows) / 2.0], np.float64)


# -- parameter spaces ---------------------------------------------------------

def to_unit(raw: np.ndarray, space) -> np.ndarray:
    raw = np.asarray(raw, np.float64)
    out = np.empty_like(raw)
    for i, (kind, lo, hi, log) in enumerate(space):
        r = raw[..., i]
        if kind == "bool":
            out[..., i] = r
        elif log:
            out[..., i] = (np.log(np.clip(r, lo, hi)) - np.log(lo)) \
                / (np.log(hi) - np.log(lo))
        else:
            out[..., i] = (np.clip(r, lo, hi) - lo) / (hi - lo)
    return out


def resource_rate(tc_raw: np.ndarray, cost: Dict[str, float]) -> np.ndarray:
    """$ per second of the cluster that raw theta_c rows allocate."""
    k1, k2, k3 = tc_raw[..., 0], tc_raw[..., 1], tc_raw[..., 2]
    return (k1 * k3 * cost["price_core_h"]
            + k2 * k3 * cost["price_mem_gb_h"]) / 3600.0


# -- the model ---------------------------------------------------------------

class _Numpy:
    """float64 numpy arithmetic."""
    xp = np

    @staticmethod
    def mm(a, b):
        return a @ b

    @staticmethod
    def es(spec, *a):
        return np.einsum(spec, *a)


class _Jax:
    """float32 jax.numpy arithmetic at one matmul precision."""

    def __init__(self, precision):
        import jax.numpy as jnp
        self.xp = jnp
        self.precision = precision

    def mm(self, a, b):
        return self.xp.matmul(a, b, precision=self.precision)

    def es(self, spec, *a):
        return self.xp.einsum(spec, *a, precision=self.precision)


class _Split:
    """float32 jax.numpy arithmetic whose matrix products take ``passes``
    bfloat16 products, as the TPU's reduced matmul precisions do: 3 is
    ``Precision.HIGH`` (hi*hi + hi*lo + lo*hi of a two-term bfloat16
    split), 1 is ``Precision.DEFAULT`` (hi*hi).  Written out, so that it
    computes the same on any backend."""

    def __init__(self, passes: int):
        import jax.numpy as jnp
        self.xp = jnp
        self.passes = passes

    def _parts(self, x):
        jnp = self.xp
        x = jnp.asarray(x, jnp.float32)
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def _products(self, f, a, b):
        (ah, al), (bh, bl) = self._parts(a), self._parts(b)
        out = f(ah, bh)
        if self.passes == 3:
            out = out + f(ah, bl) + f(al, bh)
        return out

    def mm(self, a, b):
        jnp = self.xp
        return self._products(lambda x, y: jnp.matmul(
            x, y, preferred_element_type=jnp.float32), a, b)

    def es(self, spec, *a):
        jnp = self.xp
        return self._products(lambda x, y: jnp.einsum(
            spec, x, y, preferred_element_type=jnp.float32), *a)


def arith(precision=None):
    """numpy float64 when ``precision`` is None; ``"high"`` / ``"default"``
    for the split bfloat16 products of ``_Split``; else jax float32 at that
    ``jax.lax.Precision``."""
    if precision is None:
        return _Numpy()
    if isinstance(precision, str):
        return _Split({"high": 3, "default": 1}[precision])
    return _Jax(precision)


def _gelu(xp, x):
    return x * 0.5 * (1.0 + xp.tanh(np.sqrt(2.0 / np.pi)
                                    * (x + 0.044715 * x ** 3)))


def _dense(a, p, x):
    return a.mm(x, p["w"]) + p["b"]


def _mlp(a, p, x):
    n = len(p)
    for i in range(n):
        x = _dense(a, p[f"l{i}"], x)
        if i < n - 1:
            x = _gelu(a.xp, x)
    return x


def _layernorm(a, p, x, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / a.xp.sqrt(var + eps) * p["g"] + p["b"]


def embed(a, p: dict, X, pe, bias, mask, n_heads: int):
    """(B, N, .) graphs -> (B, d_model) embeddings."""
    xp = a.xp
    h = _dense(a, p["in_proj"], X) + _dense(a, p["pe_proj"], pe)
    B, N, d = h.shape
    dh = d // n_heads
    neg = xp.where(mask[:, None, None, :], 0.0, -1e9)
    n_layers = sum(1 for k in p if k.startswith("layer"))
    for i in range(n_layers):
        lp = p[f"layer{i}"]
        qkv = _dense(a, lp["qkv"], _layernorm(a, lp["ln1"], h))
        qkv = qkv.reshape(B, N, 3, n_heads, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        logits = a.es("bnhd,bmhd->bhnm", q, k) / np.sqrt(dh) \
            + a.es("bnmf,hf->bhnm", bias, lp["bias"]) + neg
        logits = logits - logits.max(-1, keepdims=True)
        w = xp.exp(logits)
        w = w / w.sum(-1, keepdims=True)
        ctx = a.es("bhnm,bmhd->bnhd", w, v).reshape(B, N, d)
        h = h + _dense(a, lp["out"], ctx)
        h = h + _mlp(a, lp["ffn"], _layernorm(a, lp["ln2"], h))
    wm = mask.astype(h.dtype)
    return (h * wm[..., None]).sum(1) / xp.maximum(wm.sum(1), 1.0)[:, None]


def head(a, p: dict, emb, theta, nond):
    """Regressor rows -> z-space [latency, IO]."""
    return _mlp(a, p, a.xp.concatenate([emb, theta, nond], axis=-1))


def as_float64(params):
    """A parameter tree as numpy float64 (for the numpy reference)."""
    if isinstance(params, dict):
        return {k: as_float64(v) for k, v in params.items()}
    return np.asarray(params, np.float64)


def from_z(z: np.ndarray, stats: np.ndarray) -> np.ndarray:
    mu, sd = stats
    return np.maximum(np.exp(z * sd + mu) - TARGET_EPS, 0.0)


def chosen_objectives(query, ct, params: dict, stats: np.ndarray,
                      cost: Dict[str, float], n_heads: int,
                      a=None) -> np.ndarray:
    """[latency, dollars] that the reference model gives the served choice.

    Sum over subQs of the subQ model's prediction at the chosen theta_c and
    that subQ's theta_p / theta_s, with CBO statistics, as the
    compile-time objective defines it; computed in numpy float64, or with
    the arithmetic ``a``.
    """
    a = a or _Numpy()
    m = query.n_subqs
    X, pe, bias, mask = stack_graphs(
        [subq_graph(query, i, use_est=True) for i in range(m)])
    tc = to_unit(np.asarray(ct.theta_c)[None, :], THETA_C)
    theta = np.concatenate([np.repeat(tc, m, 0),
                            to_unit(ct.theta_p_sub, THETA_P),
                            to_unit(ct.theta_s_sub, THETA_S)], -1)
    nond = np.zeros((m, NOND_DIM))
    for i, sq in enumerate(query.subqs):
        nond[i, :5] = alpha_stats(sq.est_input_rows, sq.est_input_bytes)
    # The model reads its inputs as float32.
    dt = np.float64 if isinstance(a, _Numpy) else np.float32

    def f(x):
        return np.asarray(x, np.float32).astype(dt)
    emb = embed(a, params["gtn"], f(X), f(pe), f(bias), mask, n_heads)
    z = np.asarray(head(a, params["reg"], emb, f(theta), f(nond)), np.float64)
    y = from_z(z, np.asarray(stats, np.float64))
    lat, io = y[:, 0], y[:, 1]
    dollars = lat * resource_rate(np.asarray(ct.theta_c, np.float64), cost) \
        + io * cost["price_io_gb"]
    return np.array([lat.sum(), dollars.sum()])
