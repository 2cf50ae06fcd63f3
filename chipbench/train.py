"""The served models' weights, trained by the benchmark from a fixed seed.

The ``subq`` and ``qs`` performance models are trained on the chip with
the benchmark's own copy of the recipe of ``repro.core.models.training``
(8:1:1 split by query, AdamW on the Huber loss of z-normalised log
targets, 100 warm-up steps and a cosine decay), on traces that the
program's cluster simulator collects for the configuration's own
templates.  The forward pass is ``reference.embed`` / ``reference.head``
at ``Precision.HIGHEST``.  The trained parameters are cached under
``chipbench/.models/``, keyed by the configuration file and by this
file's and the reference's source, so only a checkout's first run of a
configuration trains.
"""
from __future__ import annotations

import hashlib
import os
import time
from typing import Dict, Tuple

import numpy as np

from . import reference as R
from .queries import make_query

HERE = os.path.dirname(os.path.abspath(__file__))
MODEL_DIR = os.path.join(HERE, ".models")
KINDS = ("subq", "qs")


def init_params(key, gtn: dict, hidden, theta_dim: int, n_targets: int):
    """Random parameters in the program's tree layout."""
    import jax
    import jax.numpy as jnp

    def dense(k, d_in, d_out, scale=1.0):
        return {"w": jax.random.normal(k, (d_in, d_out)) * scale
                / np.sqrt(d_in), "b": jnp.zeros((d_out,))}

    def mlp(k, dims):
        ks = jax.random.split(k, len(dims) - 1)
        return {f"l{i}": dense(kk, dims[i], dims[i + 1])
                for i, kk in enumerate(ks)}

    d = gtn["d_model"]
    k_gtn, k_reg = jax.random.split(key)
    ks = jax.random.split(k_gtn, 2 + gtn["n_layers"])
    g = {"in_proj": dense(ks[0], gtn["feat_dim"], d),
         "pe_proj": dense(ks[1], gtn["pe_dim"], d, scale=0.5)}
    for i, k in enumerate(ks[2:]):
        kk = jax.random.split(k, 5)
        g[f"layer{i}"] = {
            "qkv": dense(kk[0], d, 3 * d), "out": dense(kk[1], d, d),
            "bias": 0.1 * jax.random.normal(kk[2], (gtn["n_heads"], 3)),
            "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
            "ffn": mlp(kk[3], [d, gtn["d_ff"], d])}
    reg_in = d + theta_dim + R.NOND_DIM
    return {"gtn": g, "reg": mlp(k_reg, [reg_in, *hidden, n_targets])}


def _dataset(traces, kind: str, seed: int):
    """Rows of one model: graph index, theta, nondecision, targets, train."""
    use_est = kind == "subq"
    keys: Dict[Tuple[int, int], int] = {}
    graphs = []
    gid = np.zeros(traces.query_idx.shape[0], int)
    for r, (qi, si) in enumerate(zip(traces.query_idx, traces.subq_idx)):
        k = (int(qi), int(si))
        if k not in keys:
            keys[k] = len(graphs)
            graphs.append(R.subq_graph(traces.queries[qi], si,
                                       use_est=use_est))
        gid[r] = keys[k]
    S = gid.shape[0]
    if kind == "subq":
        theta = np.concatenate([traces.theta_c, traces.theta_p,
                                traces.theta_s], -1)
        nond = np.concatenate([traces.alpha_cbo, np.zeros((S, 7))], -1)
    else:
        theta = np.concatenate([traces.theta_c, traces.theta_s], -1)
        nond = np.concatenate([traces.alpha_true, traces.beta,
                               traces.gamma], -1)
    nq = len(traces.queries)
    perm = np.random.default_rng(seed).permutation(nq)
    train_q = set(perm[:int(0.8 * nq)].tolist())
    train = np.array([qi in train_q for qi in traces.query_idx])
    return (R.stack_graphs(graphs), gid, theta.astype(np.float32),
            nond.astype(np.float32), traces.y_subq.astype(np.float32), train)


def train_kind(traces, kind: str, cfg: dict) -> Tuple[dict, np.ndarray]:
    import jax
    import jax.numpy as jnp

    tr, mc = cfg["training"], cfg["model"]
    steps, seed = cfg["train_steps"], tr["seed"]
    G, gid, theta, nond, y, train = _dataset(traces, kind, seed)
    logy = np.log(np.maximum(y[train], 0.0) + R.TARGET_EPS)
    stats = np.stack([logy.mean(0), np.maximum(logy.std(0), 1e-3)])
    z_all = ((np.log(np.maximum(y, 0.0) + R.TARGET_EPS) - stats[0])
             / stats[1]).astype(np.float32)
    a = R.arith(jax.lax.Precision.HIGHEST)
    n_heads = mc["gtn"]["n_heads"]
    params = init_params(jax.random.PRNGKey(seed), mc["gtn"], mc["hidden"],
                         theta.shape[1], mc["n_targets"])
    opt = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
           "v": jax.tree_util.tree_map(jnp.zeros_like, params)}

    def loss_fn(p, g, th, nd, z):
        emb = R.embed(a, p["gtn"], *g, n_heads)
        r = jnp.abs(R.head(a, p["reg"], emb, th, nd) - z)
        return jnp.where(r <= 1.0, 0.5 * r * r, r - 0.5).mean()

    @jax.jit
    def step(p, opt, t, lr, g, th, nd, z):
        loss, grad = jax.value_and_grad(loss_fn)(p, g, th, nd, z)
        m = jax.tree_util.tree_map(lambda m_, g_: 0.9 * m_ + 0.1 * g_,
                                   opt["m"], grad)
        v = jax.tree_util.tree_map(lambda v_, g_: 0.999 * v_ + 0.001 * g_ * g_,
                                   opt["v"], grad)
        mh, vh = 1.0 / (1 - 0.9 ** t), 1.0 / (1 - 0.999 ** t)
        p = jax.tree_util.tree_map(
            lambda p_, m_, v_: p_ - lr * (m_ * mh / (jnp.sqrt(v_ * vh) + 1e-8)
                                          + 1e-4 * p_), p, m, v)
        return p, {"m": m, "v": v}, loss

    rng = np.random.default_rng(seed)
    idx_all = np.nonzero(train)[0]
    batch = min(tr["batch"], idx_all.size)
    for t in range(steps):
        idx = rng.choice(idx_all, size=batch, replace=idx_all.size < 2 * batch)
        gi = gid[idx]
        lr = tr["lr"] * min(1.0, (t + 1) / 100.0) \
            * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * t / steps)))
        params, opt, _ = step(params, opt, np.float32(t + 1), np.float32(lr),
                              tuple(x[gi] for x in G), theta[idx], nond[idx],
                              z_all[idx])
    return params, stats


def _cache_path(cfg_text: str) -> str:
    h = hashlib.sha256(cfg_text.encode())
    for name in ("train.py", "reference.py", "queries.py"):
        with open(os.path.join(HERE, name), "rb") as f:
            h.update(f.read())
    return os.path.join(MODEL_DIR, f"{h.hexdigest()[:16]}.npz")


def _flatten(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
        return out
    return {prefix[:-1]: np.asarray(tree)}


def _unflatten(flat):
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def trained_models(cfg: dict, cfg_text: str, log) -> Tuple[Dict[str, dict],
                                                          Dict[str, np.ndarray],
                                                          Dict[str, float]]:
    """(params, target stats, seconds spent) of both kinds, from the cache
    or trained now; params are numpy float32 trees."""
    path = _cache_path(cfg_text)
    secs = {"traces": 0.0, "training": 0.0}
    if os.path.exists(path):
        with np.load(path) as data:
            flat = {k: data[k] for k in data.files}
        params = {k: _unflatten({p[len(k) + 1:]: v for p, v in flat.items()
                                 if p.startswith(k + "/")}) for k in KINDS}
        stats = {k: flat[f"stats.{k}"] for k in KINDS}
        return params, stats, secs
    from repro.queryengine.trace import collect_traces

    t0 = time.perf_counter()
    wl, tr = cfg["workload"], cfg["training"]
    queries = [make_query(wl, t, v) for t in range(wl["n_templates"])
               for v in range(1, 1 + tr["variants"])]
    traces = collect_traces(queries, tr["confs"], seed=tr["seed"])
    secs["traces"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    params, stats, flat = {}, {}, {}
    for kind in KINDS:
        p, s = train_kind(traces, kind, cfg)
        params[kind] = _unflatten(_flatten(p))
        stats[kind] = s
        flat.update({f"{kind}/{k}": v for k, v in _flatten(p).items()})
        flat[f"stats.{kind}"] = s
    secs["training"] = time.perf_counter() - t0
    os.makedirs(MODEL_DIR, exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, **flat)
    os.replace(tmp, path)
    log(f"[setup] trained {KINDS} for {cfg['train_steps']} steps each on "
        f"{traces.query_idx.shape[0]} trace rows: {secs}")
    return params, stats, secs
