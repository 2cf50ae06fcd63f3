"""Minimal neural-net building blocks in raw JAX (no flax/optax on box).

Parameters are nested dicts of jnp arrays ("pytrees").  Everything here is
jit/vmap-friendly and deterministic given a PRNGKey.

Matmuls run at :data:`MATMUL_PRECISION` (float32 operands kept float32).
The TPU's default precision rounds float32 operands to bfloat16, so
without it the same model would predict different objectives on the chip
than on a CPU host.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]

__all__ = ["dense_init", "dense", "mlp_init", "mlp", "layernorm_init",
           "layernorm", "adamw_init", "adamw_update", "tree_l2",
           "MATMUL_PRECISION"]

MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def dense_init(key: jax.Array, d_in: int, d_out: int,
               scale: float = 1.0) -> Params:
    w = jax.random.normal(key, (d_in, d_out)) * scale / np.sqrt(d_in)
    return {"w": w, "b": jnp.zeros((d_out,))}


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(x, p["w"], precision=MATMUL_PRECISION) + p["b"]


def mlp_init(key: jax.Array, dims: Sequence[int]) -> Params:
    keys = jax.random.split(key, len(dims) - 1)
    return {f"l{i}": dense_init(k, dims[i], dims[i + 1])
            for i, k in enumerate(keys)}


def mlp(p: Params, x: jnp.ndarray,
        act: Callable = jax.nn.gelu) -> jnp.ndarray:
    n = len(p)
    for i in range(n):
        x = dense(p[f"l{i}"], x)
        if i < n - 1:
            x = act(x)
    return x


def layernorm_init(d: int) -> Params:
    return {"g": jnp.ones((d,)), "b": jnp.zeros((d,))}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


# ---------------------------------------------------------------------------
# AdamW (pytree optimizer)
# ---------------------------------------------------------------------------

def adamw_init(params: Params) -> Params:
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": z, "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adamw_update(params: Params, grads: Params, state: Params, lr: float,
                 *, b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                 wd: float = 1e-4) -> Tuple[Params, Params]:
    t = state["t"] + 1
    m = jax.tree_util.tree_map(lambda m_, g: b1 * m_ + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                               state["v"], grads)
    mhat_scale = 1.0 / (1 - b1 ** t)
    vhat_scale = 1.0 / (1 - b2 ** t)

    def upd(p, m_, v_):
        return p - lr * (m_ * mhat_scale / (jnp.sqrt(v_ * vhat_scale) + eps)
                         + wd * p)

    new_params = jax.tree_util.tree_map(upd, params, m, v)
    return new_params, {"m": m, "v": v, "t": t}


def tree_l2(tree: Params) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l * l) for l in leaves))
