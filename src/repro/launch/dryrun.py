import os
# The env reads/write below must run before the first jax-touching import:
# jax locks the host platform device count at first init, so import-time
# module scope is the only place this works — suppressed by design.
_flags = os.environ.get("XLA_FLAGS", "")  # repro: allow[TH003] pre-jax-init by design
_n_dev = os.environ.get("DRYRUN_DEVICES", "512")  # repro: allow[TH003] pre-jax-init by design
os.environ["XLA_FLAGS"] = (  # repro: allow[TH003] pre-jax-init by design
    _flags + " --xla_force_host_platform_device_count=" + _n_dev).strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(...).compile()`` must succeed on the
single-pod (16, 16) and multi-pod (2, 16, 16) production meshes for every
assigned architecture × input shape, with ``memory_analysis()`` showing the
per-device footprint fits HBM and ``cost_analysis()`` + HLO collective
parsing feeding the §Roofline table.

The XLA_FLAGS assignment above MUST run before any other jax-touching
import — jax locks the device count at first init.  Set DRYRUN_DEVICES to
override (e.g. 8 for a fast sanity pass with a (2,2,2)/(4,2) mesh).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b \
      --shape train_4k [--multi-pod] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..archs.registry import ARCH_IDS, build_model, get_config
from ..launch.hlo_analysis import (collective_bytes, hlo_flops_bytes,
                                   roofline_terms)
from ..launch.mesh import auto_mesh
from ..launch.shapes import (SHAPES, ShapeCell, cell_applicable,
                             serve_input_specs, train_input_specs)
from ..train.optimizer import OptConfig, opt_init
from ..train.serve import make_serve_fns
from ..train.train_loop import make_train_step

__all__ = ["dryrun_cell", "main", "make_meshes"]


def make_meshes(multi_pod: bool):
    """Production meshes, shrunk proportionally when DRYRUN_DEVICES≠512."""
    n = len(jax.devices())
    if n >= 512:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    elif n >= 8:
        if multi_pod:
            m = n // 2
            a = int(2 ** np.floor(np.log2(np.sqrt(m))))
            shape = (2, max(m // a, 1), a)
        else:
            a = int(2 ** np.floor(np.log2(np.sqrt(n))))
            shape = (max(n // a, 1), a)
    else:
        shape = (1, n) if not multi_pod else (1, 1, n)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def _active_params(cfg, params_shape) -> float:
    """Active parameter count (MoE experts weighted by k/E)."""
    total = 0.0
    frac = cfg.top_k / cfg.n_experts if cfg.n_experts else 1.0
    for kp, x in jax.tree_util.tree_flatten_with_path(params_shape)[0]:
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        n = float(np.prod(x.shape))
        if any(s in path for s in ("e_gate", "e_up", "e_down")):
            n *= frac
        total += n
    return total


def dryrun_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
                accum: Optional[int] = None,
                overrides: Optional[Dict[str, Any]] = None,
                verbose: bool = True) -> Dict[str, Any]:
    cell = SHAPES[shape_name]
    cfg = get_config(arch_id, **(overrides or {}))
    if accum is None:
        accum = cfg.train_accum
    if not cell_applicable(cfg, shape_name):
        return {"arch": arch_id, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch: long_500k requires "
                          "sub-quadratic attention (DESIGN.md)"}
    mesh = make_meshes(multi_pod)
    api = build_model(cfg)
    t0 = time.perf_counter()
    out: Dict[str, Any] = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod, "status": "ok",
    }
    try:
        params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
        if cell.kind == "train":
            batch_sds = train_input_specs(cfg, cell)
            opt_cfg = OptConfig(moment_dtype=cfg.moment_dtype)
            fns = make_train_step(api, mesh, batch_sds, opt_cfg,
                                  accum=accum, donate=True)
            opt_shape = jax.eval_shape(
                lambda p: opt_init(p, opt_cfg), params_shape)
            lowered = fns.step.lower(params_shape, opt_shape, batch_sds)
            tokens = cell.global_batch * cell.seq_len
            flops_factor = 6.0
        else:
            # VLM prefill writes patch + token KV: size the cache for both.
            max_len = cell.seq_len + (cfg.n_patches
                                      if cfg.family == "vlm" else 0)
            sf = make_serve_fns(api, mesh, batch=cell.global_batch,
                                max_len=max_len)
            cache_shape = jax.eval_shape(
                lambda: api.init_cache(cell.global_batch, max_len))
            ins = serve_input_specs(cfg, cell)
            if cell.kind == "prefill":
                lowered = sf.prefill.lower(
                    params_shape, ins["tokens"], cache_shape,
                    ins.get("patches"))
                tokens = cell.global_batch * cell.seq_len
                flops_factor = 2.0
            else:
                lowered = sf.decode.lower(
                    params_shape, ins["tokens"], cache_shape,
                    ins["positions"])
                tokens = cell.global_batch * 1
                flops_factor = 2.0
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        hlo = compiled.as_text()
        coll_total, coll_by_type = collective_bytes(hlo)
        n_chips = int(np.prod(mesh.devices.shape))

        # Loop-aware FLOPs/bytes from the partitioned HLO (cost_analysis
        # does not weight while-loop bodies by trip count — see
        # hlo_analysis.hlo_flops_bytes).  Per-device numbers.
        flops_per_dev, bytes_per_dev, _ = hlo_flops_bytes(hlo)
        flops_total = flops_per_dev * n_chips
        bytes_total = bytes_per_dev * n_chips
        # coll_total is parsed from one device's partitioned module (per-chip
        # link traffic); roofline_terms expects the global total.
        terms = roofline_terms(flops_total, bytes_total,
                               coll_total * n_chips, n_chips)

        n_active = _active_params(cfg, params_shape)
        model_flops = flops_factor * n_active * tokens
        out.update({
            "t_lower_s": round(t_lower, 2),
            "t_compile_s": round(t_compile, 2),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
                "peak_per_device_gb": round(
                    (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes) / 1e9, 3),
            },
            "flops_per_device": flops_per_dev,
            "bytes_per_device": bytes_per_dev,
            "collective_bytes_per_device": coll_total,
            "collective_by_type": coll_by_type,
            "roofline": {
                "compute_s": terms.compute_s,
                "memory_s": terms.memory_s,
                "collective_s": terms.collective_s,
                "dominant": terms.dominant,
                "bound_s": terms.bound_s,
            },
            "model_flops": model_flops,
            "n_active_params": n_active,
            "useful_flops_ratio": (model_flops / flops_total
                                   if flops_total else 0.0),
            "tokens_per_step": tokens,
        })
        if verbose:
            r = out["roofline"]
            print(f"[{arch_id} × {shape_name} × {out['mesh']}] "
                  f"compile {t_compile:.1f}s | "
                  f"peak/dev {out['memory']['peak_per_device_gb']:.2f} GB | "
                  f"compute {r['compute_s']*1e3:.2f} ms, "
                  f"memory {r['memory_s']*1e3:.2f} ms, "
                  f"collective {r['collective_s']*1e3:.2f} ms "
                  f"→ {r['dominant']}-bound | "
                  f"useful-FLOPs {out['useful_flops_ratio']:.2f}")
    except Exception as exc:  # noqa: BLE001 — record failures as data
        out["status"] = "error"
        out["error"] = f"{type(exc).__name__}: {exc}"
        out["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[{arch_id} × {shape_name}] FAILED: {out['error']}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    help="ArchConfig override key=value (repeatable)")
    args = ap.parse_args()

    overrides: Dict[str, Any] = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            overrides[k] = json.loads(v)
        except json.JSONDecodeError:
            overrides[k] = v

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for a, s in cells:
        res = dryrun_cell(a, s, multi_pod=args.multi_pod, accum=args.accum,
                          overrides=overrides)
        results.append(res)
        tag = "mp" if args.multi_pod else "sp"
        with open(os.path.join(args.out, f"{a}_{s}_{tag}.json"), "w") as f:
            json.dump(res, f, indent=1)
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    print(f"\n{ok} ok, {sk} skipped, {len(results)-ok-sk} failed "
          f"of {len(results)} cells")


if __name__ == "__main__":
    main()
