"""Graph Transformer Network embedder (paper §4.3, [6, 56]).

Multi-head self-attention over operator nodes with (i) learned per-head
additive biases on graph-structure flags (forward edge, backward edge, self)
and (ii) Laplacian positional encodings added to the input projection —
the Dwivedi–Bresson graph-transformer recipe.  Masked mean-pool over valid
nodes produces the plan embedding that feeds the regressor.

Pure JAX; parameters are nested dicts (see ``nn.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .features import LAPPE_K, OP_FEAT_DIM
from .nn import (MATMUL_PRECISION, Params, dense, dense_init, layernorm,
                 layernorm_init, mlp, mlp_init)

__all__ = ["GTNConfig", "gtn_init", "gtn_apply", "gtn_apply_batch"]


@dataclasses.dataclass(frozen=True)
class GTNConfig:
    d_model: int = 48
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 96
    feat_dim: int = OP_FEAT_DIM
    pe_dim: int = LAPPE_K


def gtn_init(key: jax.Array, cfg: GTNConfig) -> Params:
    keys = jax.random.split(key, 2 + cfg.n_layers)
    p: Params = {
        "in_proj": dense_init(keys[0], cfg.feat_dim, cfg.d_model),
        "pe_proj": dense_init(keys[1], cfg.pe_dim, cfg.d_model, scale=0.5),
    }
    for i, k in enumerate(keys[2:]):
        ks = jax.random.split(k, 5)
        p[f"layer{i}"] = {
            "qkv": dense_init(ks[0], cfg.d_model, 3 * cfg.d_model),
            "out": dense_init(ks[1], cfg.d_model, cfg.d_model),
            "bias": 0.1 * jax.random.normal(ks[2], (cfg.n_heads, 3)),
            "ln1": layernorm_init(cfg.d_model),
            "ln2": layernorm_init(cfg.d_model),
            "ffn": mlp_init(ks[3], [cfg.d_model, cfg.d_ff, cfg.d_model]),
        }
    return p


def gtn_apply(p: Params, cfg: GTNConfig, X: jnp.ndarray, pe: jnp.ndarray,
              bias: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """One graph -> (d_model,) embedding.

    X: (N, F), pe: (N, K), bias: (N, N, 3) structure flags, mask: (N,).
    """
    N = X.shape[0]
    h = dense(p["in_proj"], X) + dense(p["pe_proj"], pe)
    dh = cfg.d_model // cfg.n_heads
    neg = jnp.float32(-1e9)
    attn_mask = jnp.where(mask[None, :], 0.0, neg)  # (1, N) key mask

    for i in range(cfg.n_layers):
        lp = p[f"layer{i}"]
        hn = layernorm(lp["ln1"], h)
        qkv = dense(lp["qkv"], hn).reshape(N, 3, cfg.n_heads, dh)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]        # (N, H, dh)
        logits = jnp.einsum("nhd,mhd->hnm", q, k,
                            precision=MATMUL_PRECISION) / jnp.sqrt(dh)
        struct = jnp.einsum("nmf,hf->hnm", bias, lp["bias"],
                            precision=MATMUL_PRECISION)
        logits = logits + struct + attn_mask[None, :, :]
        w = jax.nn.softmax(logits, axis=-1)
        ctx = jnp.einsum("hnm,mhd->nhd", w, v,
                         precision=MATMUL_PRECISION).reshape(N, cfg.d_model)
        h = h + dense(lp["out"], ctx)
        hn = layernorm(lp["ln2"], h)
        h = h + mlp(lp["ffn"], hn)

    w = mask.astype(h.dtype)
    return (h * w[:, None]).sum(0) / jnp.maximum(w.sum(), 1.0)


def gtn_apply_batch(p: Params, cfg: GTNConfig, X: jnp.ndarray,
                    pe: jnp.ndarray, bias: jnp.ndarray,
                    mask: jnp.ndarray) -> jnp.ndarray:
    """(B, N, ·) batch -> (B, d_model)."""
    return jax.vmap(lambda x, e, b, m: gtn_apply(p, cfg, x, e, b, m))(
        X, pe, bias, mask)
