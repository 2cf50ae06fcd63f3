"""``chip_smoke.py``'s phases at a tiny size on the CPU (kernels in
interpret mode), and its refusal to report a result without a TPU."""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.moo.hmooc import HMOOCConfig

ROOT = Path(__file__).resolve().parents[1]

CFG = HMOOCConfig(n_c_init=16, n_clusters=4, n_p_pool=48, n_c_enrich=12,
                  max_bank=12, seed=3)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def traces(smoke):
    return smoke.smoke_traces(variants=1, confs=4)


@pytest.fixture(scope="module")
def models(smoke, traces):
    models, info = smoke.train_models(traces, steps=4)
    return models, info


def test_kernels_phase_matches_refs(smoke):
    shapes = {"pareto_n": 200, "ws_m": 5, "bank": 12, "n_weights": 11,
              "fused_n": 7, "fused_m": 5}
    out = smoke.run_kernels(0, interpret=True, shapes=shapes)
    assert set(out) == {"pareto_filter", "ws_reduce", "fused_ws_front"}
    assert out["pareto_filter"]["front"] > 0
    assert out["fused_ws_front"]["front"] > 0


def test_tie_free_banks_separate_best_two(smoke):
    rng = np.random.default_rng(0)
    W = np.stack([np.linspace(0, 1, 11), 1 - np.linspace(0, 1, 11)], 1)
    F = smoke._tie_free_banks(rng, (3, 40, 48, 2), W)
    assert F.dtype == np.float32 and F.shape == (3, 40, 48, 2)
    s = np.einsum("wk,nbk->wnb", W.astype(np.float32).astype(np.float64),
                  F.reshape(-1, 48, 2).astype(np.float64))
    two = np.sort(s, axis=-1)[..., :2]
    assert (two[..., 1] - two[..., 0] >= 1e-4).all()


def test_models_phase_default_widths(models):
    trained, info = models
    for kind in ("subq", "qs"):
        gtn = trained[kind].cfg.gtn
        assert (gtn.d_model, gtn.n_heads, gtn.n_layers, gtn.d_ff) == \
            (48, 4, 2, 96)
        assert trained[kind].cfg.hidden == (128, 96)
        assert info[kind]["steps"] == 4


def test_device_vs_cpu_check(smoke, models, traces):
    trained, _ = models
    diffs = smoke.device_vs_cpu(trained, traces, n_rows=40)
    assert set(diffs) == {"subq", "qs"}
    for d in diffs.values():   # the default device is the CPU here
        assert d == {"embed": 0.0, "predict": 0.0}
    # The served models' own embedding memos are left untouched.
    assert not any(m._emb_cache for m in trained.values())


def test_serve_phase_matches_sequential_reference(smoke, models):
    trained, _ = models
    out = smoke.serve_and_check(trained, cfg=CFG, n_tpch=4, n_tpcds=1,
                                seed=0)
    assert out["requests"] == {"tpch": 4, "tpcds": 1}
    assert out["max_subqs"] > 32          # pads to the 64-subQ bucket
    routes = out["routes"]
    assert routes["pareto"]["numpy"] > 0  # CPU thresholds keep numpy
    assert routes["pareto"]["kernel"] == 0
    assert out["compile_stats"]["subq"]["head_buckets"]
    # The serve() record counts every compile under a program span.
    assert all(k.startswith("repro.") for k in out["compiles_by_span"])


def test_count_routes_restores_entry_points(smoke):
    from repro.core.moo import hmooc, pareto
    from repro.kernels import pareto_filter
    before = (pareto.pareto_mask_fast, hmooc.pareto_mask_fast,
              hmooc._ws_min_scores, pareto_filter.pareto_filter)
    with smoke.count_routes() as counts:
        pareto.pareto_mask_fast(np.random.default_rng(0).random((8, 2)))
        hmooc._ws_min_scores()
    assert counts["pareto_decisions"] == 1 and counts["ws_decisions"] == 1
    assert (pareto.pareto_mask_fast, hmooc.pareto_mask_fast,
            hmooc._ws_min_scores, pareto_filter.pareto_filter) == before


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
