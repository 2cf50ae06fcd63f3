"""The harness end to end on the CPU at a tiny size.

``run.py`` must refuse to run without a TPU.  With its look for a chip
skipped, a run at a tiny size must come out correct, and must come out not
correct when the timed path is broken underneath it (an answer altered
where it is produced) or when a lower precision takes the model's place.
"""
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import check, control, run, train

HERE = os.path.dirname(os.path.abspath(__file__))
CB = os.path.dirname(HERE)
ROOT = os.path.dirname(CB)
CELL = "tpch.fresh"


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


@pytest.mark.parametrize("layout", ["checkout", "benchmark_files_only"])
def test_exits_nonzero_without_a_tpu(layout, tmp_path):
    cwd = ROOT
    if layout == "benchmark_files_only":
        cwd = str(tmp_path)
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), cwd)
        shutil.copytree(CB, os.path.join(cwd, "chipbench"),
                        ignore=shutil.ignore_patterns(".*", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert _no_result(proc)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose cell runs the tpch_sf100 configuration with its
    model trained for a few steps and a small solver, at 2 requests/s."""
    root = tmp_path_factory.mktemp("root")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(CB, "configs", "tpch_sf100.json")) as f:
        cfg = json.load(f)
    cfg["train_steps"] = 20
    cfg["hmooc"].update(n_c_init=16, n_p_pool=64, n_c_enrich=16, max_bank=16)
    mix = {"templates": "uniform", "fresh": True, "rate_qps": 2.0,
           "warmup_s": 2.0, "mix_seed": 1}
    for sub in ("configs", "traffic"):
        os.makedirs(root / "chipbench" / sub)
    (root / "chipbench" / "configs" / "tpch_sf100.json").write_text(
        json.dumps(cfg))
    (root / "chipbench" / "traffic" / "tpch_fresh.json").write_text(
        json.dumps(mix))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "peaks.json").write_text(json.dumps(
        {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}))
    return root


@pytest.fixture
def harness(tiny_root, monkeypatch):
    """``run`` with the look for a chip skipped and its files in the
    tiny checkout."""
    import jax
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices()[0])
    monkeypatch.setattr(run, "setup_compile_cache", lambda: "off")
    monkeypatch.setattr(run, "PEAKS", str(tiny_root / "peaks.json"))
    monkeypatch.setattr(run, "ROOT", str(tiny_root))
    monkeypatch.setattr(run, "TRACE_DIR", str(tiny_root / "traces"))
    monkeypatch.setattr(train, "MODEL_DIR", str(tiny_root / "models"))
    return tiny_root


def _run(root, capsys, seed=2 ** 31 + 11):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "3",
                   "--trace", "0"], root=str(root))
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_sound_run_is_correct(harness, capsys):
    res = _run(harness, capsys)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 6
    assert set(res["metrics"]) == {"setup_s", "solve_p50_s", "solve_p95_s",
                                   "plan_p95_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(check.LIMITS)


def _scale_predictions(monkeypatch):
    from repro.core.models.perf_model import PerfModel
    orig = PerfModel.predict_rows

    def altered(self, emb, theta, nond):
        return orig(self, emb, theta, nond) * 1.01
    monkeypatch.setattr(PerfModel, "predict_rows", altered)


def _alter_theta(monkeypatch):
    from repro.serve import TuningService
    orig = TuningService.tune_batch

    def altered(self, queries, *a, **kw):
        out = orig(self, queries, *a, **kw)
        for ct in out:
            ct.theta_p_sub = np.array(ct.theta_p_sub, copy=True)
            ct.theta_p_sub[:, 0] = np.maximum(ct.theta_p_sub[:, 0] * 0.5, 8)
        return out
    monkeypatch.setattr(TuningService, "tune_batch", altered)


def _alter_plan(monkeypatch):
    from repro.serve import RuntimeSession
    orig = RuntimeSession.realize

    def altered(self, done):
        out = orig(self, done)
        for res in out:
            res.sim = copy.copy(res.sim)
            res.sim.actual_latency = res.sim.actual_latency * 1.01
        return out
    monkeypatch.setattr(RuntimeSession, "realize", altered)


@pytest.mark.parametrize("fault", [_scale_predictions, _alter_theta,
                                   _alter_plan],
                         ids=["model_output", "compile_time_theta",
                              "runtime_plan"])
def test_answer_altered_where_produced_is_not_correct(fault, harness, capsys,
                                                      monkeypatch):
    fault(monkeypatch)
    res = _run(harness, capsys)
    assert res["correct"] is False, res["checks"]


def test_precision_controls(harness):
    """The program passes; the reference computed in the program's place
    with three bfloat16 products per matrix product (``Precision.HIGH``)
    fails, and so does one (``DEFAULT``)."""
    spec = run.load_cell(CELL)
    cell = run.Cell(spec)
    cell.setup()
    seed = 2 ** 31 + 21
    cell.warm(seed, 2.0)
    out = control.read_seed(cell, seed, 3.0)
    assert out["correct"]["program"] is True, out
    assert out["correct"]["high"] is False, out
    assert out["correct"]["default"] is False, out
