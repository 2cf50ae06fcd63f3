"""GTN-embedder + regressor performance models (paper Fig. 6).

One :class:`PerfModel` per modeling target:

* ``subq`` — compile time: subQ operator group with CBO cardinalities;
  decision vars θc⊕θp⊕θs (19); α from CBO, β = 0, γ = 0.
* ``qs``   — runtime query stage: true cardinalities; θp dropped (already
  fixed when a QS is optimized) → θc⊕θs (10); α/β/γ observed.
* ``lqp``  — runtime collapsed plan: whole-plan graph; θc⊕θp⊕θs; predicts
  end-to-end latency of the (remaining) plan.

Targets are predicted in log1p space: [latency (s), IO (GB)].

The embedding of a plan/subQ does not depend on θ, so MOO solving caches the
GTN output once per (query, stage) and sweeps thousands of θ rows through the
small regressor — this is what makes sub-second solving feasible (paper's
60–462K inference/s).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...queryengine.plan import Query
from .features import batch_graphs, featurize_plan, featurize_subq
from .gtn import GTNConfig, gtn_apply, gtn_apply_batch, gtn_init
from .nn import Params, mlp, mlp_init

__all__ = ["ModelConfig", "PerfModel", "NONDECISION_DIM", "pow2_bucket"]

ALPHA_DIM = 5
BETA_DIM = 3
GAMMA_DIM = 4
NONDECISION_DIM = ALPHA_DIM + BETA_DIM + GAMMA_DIM


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    kind: str                      # "subq" | "qs" | "lqp"
    theta_dim: int                 # 19 for subq/lqp, 10 for qs
    gtn: GTNConfig = GTNConfig()
    hidden: Tuple[int, ...] = (128, 96)
    n_targets: int = 2

    @property
    def reg_in(self) -> int:
        return self.gtn.d_model + self.theta_dim + NONDECISION_DIM

    @property
    def pad(self) -> int:
        return 4 if self.kind in ("subq", "qs") else 128

    @property
    def use_est(self) -> bool:
        return self.kind == "subq"


TARGET_EPS = 1e-3


# XLA's CPU backend rounds a matmul of fewer than 64 rows differently from a
# larger one, so a row's result would depend on how many rows share its
# dispatch.  Every regressor dispatch carries at least this many rows.
MIN_DISPATCH_ROWS = 64

# Node rows per GTN dispatch.  Every embedding dispatch of a model has this
# one shape (graphs chunked, the last chunk padded): on the TPU a graph's
# embedding differs in its last bits between batch shapes, so a per-query
# solve and a served micro-batch would otherwise disagree.
EMBED_CHUNK_NODES = 256


def pow2_bucket(n: int, lo: int = MIN_DISPATCH_ROWS) -> int:
    """Smallest power of two ≥ max(n, lo).

    Batched inference pads its row axis to these buckets so a serving
    session only ever compiles O(log n_max) distinct signatures per jitted
    function, however request sizes vary.
    """
    return max(lo, 1 << (max(n, 1) - 1).bit_length())


def _head_max_bucket() -> int:
    """Row cap per regressor dispatch (``REPRO_HEAD_MAX_BUCKET``).

    Fused micro-batch solves can concatenate 100k+ rows; padding that to
    the next power of two wastes up to 2× compute.  Instead the rows are
    dispatched in chunks of at most this bucket: full chunks need no
    padding at all, only the tail pads (to its own pow2 bucket ≤ the cap),
    and the compiled-signature set stays the fixed ladder {64 … cap}.
    Resolved per call so tests/benchmarks can re-tune it.
    """
    import os

    b = int(os.environ.get("REPRO_HEAD_MAX_BUCKET", "8192"))
    return pow2_bucket(b)


class PerfModel:
    """Parameter container + jitted apply/predict paths.

    Targets are modeled in z-normalized log space:
    ``z = (log(y + eps) - mu) / sd`` with (mu, sd) from the training split —
    so optimizing the loss optimizes *relative* error across scales.
    """

    def __init__(self, cfg: ModelConfig, params: Optional[Params] = None,
                 seed: int = 0,
                 target_stats: Optional[np.ndarray] = None):
        self.cfg = cfg
        if params is None:
            key = jax.random.PRNGKey(seed)
            k1, k2 = jax.random.split(key)
            params = {
                "gtn": gtn_init(k1, cfg.gtn),
                "reg": mlp_init(k2, [cfg.reg_in, *cfg.hidden, cfg.n_targets]),
            }
        self.params = params
        # (2, n_targets): row 0 = mu, row 1 = sd of log(y + eps).
        if target_stats is None:
            target_stats = np.stack([np.zeros(cfg.n_targets),
                                     np.ones(cfg.n_targets)])
        self.target_stats = np.asarray(target_stats, np.float32)
        self._emb_cache: Dict[Any, np.ndarray] = {}
        self._fp: Optional[str] = None
        # Shape buckets seen by the padded batch paths (the recompilation
        # bound the tests assert against).
        self.head_buckets: set = set()
        self.embed_buckets: set = set()

        cfg_gtn = cfg.gtn

        @jax.jit
        def _embed_batch(p, X, pe, bias, mask):
            return gtn_apply_batch(p["gtn"], cfg_gtn, X, pe, bias, mask)

        def _head_fn(p, emb, theta, nond):
            x = jnp.concatenate([emb, theta, nond], axis=-1)
            return mlp(p["reg"], x)

        self._head = jax.jit(_head_fn)
        self._embed_batch = _embed_batch

    # -- forward -------------------------------------------------------------
    def apply_rows(self, params: Params, graphs, theta: jnp.ndarray,
                   nond: jnp.ndarray) -> jnp.ndarray:
        """Training path: embed per-row graphs and regress. Returns log1p y."""
        X, pe, bias, mask = graphs
        emb = gtn_apply_batch(params["gtn"], self.cfg.gtn, X, pe, bias, mask)
        x = jnp.concatenate([emb, theta, nond], axis=-1)
        return mlp(params["reg"], x)

    # -- identity -------------------------------------------------------------
    def fingerprint(self) -> str:
        """Stable content hash of the model (params + config + target stats).

        Serving caches key entries by this instead of ``id(model)``: the
        fingerprint survives process restarts and model reloads, never pins
        the live object, and an atomically swapped-in refreshed model gets a
        different fingerprint so stale entries can never be served (see
        ``ResponseCache.clear_model``).
        """
        if self._fp is None:
            h = hashlib.sha1()
            h.update(repr(self.cfg).encode())
            h.update(np.ascontiguousarray(self.target_stats).tobytes())
            for leaf in jax.tree_util.tree_leaves(self.params):
                a = np.asarray(leaf)
                h.update(str(a.shape).encode())
                h.update(np.ascontiguousarray(a).tobytes())
            self._fp = h.hexdigest()
        return self._fp

    # -- inference -----------------------------------------------------------
    def _emb_key(self, query: Query, sq_id: Optional[int]) -> tuple:
        # repro: allow[RP004] id(query) only scopes the process-local embedding memo to one live Query object (qid alone can recur with different stats); the memo is never snapshotted and embeddings do not depend on the id value
        return (id(query), query.qid, sq_id, self.cfg.kind)

    def embed(self, query: Query, sq_id: Optional[int] = None) -> np.ndarray:
        """Cached GTN embedding for a subQ group or whole plan."""
        key = self._emb_key(query, sq_id)
        if key not in self._emb_cache:
            self.embed_many([(query, sq_id)])
        return self._emb_cache[key]

    def embed_many(self, pairs: Sequence[Tuple[Query, Optional[int]]]) -> None:
        """Fill the embedding cache for many (query, sq_id) pairs at once.

        Chunked GTN dispatches replace per-subQ calls — the cold-path
        hotspot of a model-backed micro-batch solve.  Every dispatch holds
        the same number of graphs (``EMBED_CHUNK_NODES`` node rows; the last
        chunk is padded with replicas of its first graph, sliced off
        afterwards), so one compiled signature serves every batch and an
        embedding does not depend on how many graphs share its dispatch.
        Every chunk is built before the first is dispatched, and every
        chunk is dispatched before any result is read back.
        """
        kind = self.cfg.kind
        todo = []
        seen = set()
        for query, sq_id in pairs:
            key = self._emb_key(query, sq_id)
            if key in self._emb_cache or key in seen:
                continue
            seen.add(key)
            todo.append((key, query, sq_id))
        if not todo:
            return
        b = max(1, EMBED_CHUNK_NODES // self.cfg.pad)
        self.embed_buckets.add(b)
        with obs.span("repro.model.featurize." + kind):
            if kind in ("subq", "qs"):
                graphs = [featurize_subq(query, sq_id,
                                         use_est=self.cfg.use_est,
                                         n_pad=self.cfg.pad)
                          for _, query, sq_id in todo]
            else:
                graphs = [featurize_plan(query, use_est=True,
                                         n_pad=self.cfg.pad)
                          for _, query, _ in todo]
            keys = [key for key, _, _ in todo]
            chunks, batches = [], []
            for off in range(0, len(todo), b):
                chunk = graphs[off:off + b]
                chunks.append(keys[off:off + b])
                batches.append(batch_graphs(
                    chunk + [chunk[0]] * (b - len(chunk))))
        obs.count("model.graphs." + kind, len(todo))
        obs.count("model.dispatches." + kind, len(batches))
        with obs.span("repro.model.dispatch." + kind):
            outs = [self._embed_batch(self.params, gb.X, gb.pe, gb.bias,
                                      gb.mask) for gb in batches]
        with obs.span("repro.model.readback." + kind):
            for chunk, out in zip(chunks, outs):
                emb = np.asarray(out)
                for j, key in enumerate(chunk):
                    self._emb_cache[key] = emb[j]

    # -- target transform ------------------------------------------------------
    def to_z(self, y: np.ndarray) -> np.ndarray:
        mu, sd = self.target_stats
        return (np.log(np.maximum(y, 0.0) + TARGET_EPS) - mu) / sd

    def from_z(self, z: np.ndarray) -> np.ndarray:
        mu, sd = self.target_stats
        return np.maximum(np.exp(z * sd + mu) - TARGET_EPS, 0.0)

    def predict(self, emb: np.ndarray, theta: np.ndarray,
                nond: np.ndarray) -> np.ndarray:
        """(n, θd) unit θ + (n, 12) or (12,) nondecision → (n, 2) raw targets.

        ``emb`` is one cached embedding (d,) broadcast over the rows, or a
        per-row (n, d) stack — the serving layer fuses re-scoring requests
        from different (query, stage) pairs into one call this way.

        Dispatches through :meth:`predict_rows`, so its rows equal the same
        rows inside any fused batch (see ``MIN_DISPATCH_ROWS``).
        """
        theta = np.asarray(theta, np.float32)
        n = theta.shape[0]
        nond = np.asarray(nond, np.float32)
        if nond.ndim == 1:
            nond = np.broadcast_to(nond, (n, nond.shape[0]))
        emb = np.asarray(emb, np.float32)
        embb = emb if emb.ndim == 2 \
            else np.broadcast_to(emb, (n, emb.shape[0]))
        return self.predict_rows(embb, theta, nond)

    def predict_rows(self, emb: np.ndarray, theta: np.ndarray,
                     nond: np.ndarray) -> np.ndarray:
        """Like :meth:`predict` but per-row emb/nond, bucket-padded.

        The fused solve path concatenates regressor rows from every
        (query, subQ, candidate) of a micro-batch into one call here.  Rows
        are zero-padded to a power-of-two bucket so the compile cache sees
        O(log n_max) signatures across a serving session.  Per-row outputs
        equal :meth:`predict`'s on the same rows.
        """
        emb = np.ascontiguousarray(emb, np.float32)
        theta = np.ascontiguousarray(theta, np.float32)
        nond = np.ascontiguousarray(nond, np.float32)
        n = theta.shape[0]
        if n == 0:
            return np.zeros((0, self.cfg.n_targets), np.float32)
        kind = self.cfg.kind
        cap = _head_max_bucket()
        chunks = []
        with obs.span("repro.model.pad." + kind):
            for off in range(0, n, cap):
                e = emb[off:off + cap]
                t = theta[off:off + cap]
                d = nond[off:off + cap]
                c = t.shape[0]
                # Calls larger than the cap reuse the cap signature for
                # their tail too (waste < cap rows on a multi-cap call);
                # only calls that fit in one chunk get a smaller bucket of
                # the ladder.
                b = cap if n > cap else pow2_bucket(c)
                if b != c:
                    ep = np.zeros((b, e.shape[1]), np.float32)
                    ep[:c] = e
                    tp = np.zeros((b, t.shape[1]), np.float32)
                    tp[:c] = t
                    dp = np.zeros((b, d.shape[1]), np.float32)
                    dp[:c] = d
                    e, t, d = ep, tp, dp
                self.head_buckets.add((b, theta.shape[1]))
                chunks.append((e, t, d, c))
        obs.count("model.rows." + kind, n)
        obs.count("model.dispatches." + kind, len(chunks))
        with obs.span("repro.model.dispatch." + kind):
            zs = [(self._head(self.params, e, t, d), c)
                  for e, t, d, c in chunks]
        with obs.span("repro.model.readback." + kind):
            # Read back the whole bucket and slice on the host: a slice of
            # the device array would compile for every new row count.
            outs = [np.asarray(z)[:c] for z, c in zs]
        return self.from_z(outs[0] if len(outs) == 1
                           else np.concatenate(outs, 0))

    def compile_stats(self) -> dict:
        """Shape buckets the padded batch paths have used: the bound on
        the signatures each jitted function compiles.  The compiles
        themselves are counted per span by :mod:`repro.obs`."""
        return {"head_buckets": sorted(self.head_buckets),
                "embed_buckets": sorted(self.embed_buckets)}

    # -- persistence ----------------------------------------------------------
    def save(self, path: str) -> None:
        flat, treedef = jax.tree_util.tree_flatten(self.params)
        np.savez(path, n=len(flat), target_stats=self.target_stats,
                 **{f"a{i}": np.asarray(x) for i, x in enumerate(flat)})

    @classmethod
    def load(cls, cfg: ModelConfig, path: str) -> "PerfModel":
        data = np.load(path)
        proto = cls(cfg)  # for treedef
        flat, treedef = jax.tree_util.tree_flatten(proto.params)
        loaded = [jnp.asarray(data[f"a{i}"]) for i in range(int(data["n"]))]
        params = jax.tree_util.tree_unflatten(treedef, loaded)
        return cls(cfg, params=params, target_stats=data["target_stats"])


def make_nondecision(alpha: np.ndarray, beta: Optional[np.ndarray] = None,
                     gamma: Optional[np.ndarray] = None) -> np.ndarray:
    """Assemble [α, β, γ] with paper's compile-time zeros convention."""
    alpha = np.asarray(alpha, np.float32)
    lead = alpha.shape[:-1]
    if beta is None:
        beta = np.zeros(lead + (BETA_DIM,), np.float32)
    if gamma is None:
        gamma = np.zeros(lead + (GAMMA_DIM,), np.float32)
    return np.concatenate([alpha, beta, gamma], axis=-1).astype(np.float32)
