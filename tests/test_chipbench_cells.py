"""The chip benchmark's served cells, as its harness builds them, on the CPU.

The harness's own tests (``chipbench/tests``) lie outside tier 1's
``testpaths``; these cases hold the parts that decide what the served
cells run, with the pins kept in ``chipbench/tests/test_composite.py``:

* ``mixed.fresh`` resolves to its parts' own files (whose text keys their
  trained models) and splits a 51 s window exactly 306 TPC-H : 102 TPC-DS,
  one request of each TPC-DS template; its check sample holds each part's
  longest request;
* its set-up hands the server ``{benchmark: PerfModel}`` maps, where the
  one-part cells hand it single models;
* the one-part cells' streams, check samples and model cache paths are the
  ones the harness gave before composite configurations existed.
"""
import collections
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, run, train  # noqa: E402
from chipbench.tests import test_composite as pins  # noqa: E402

SEEDS = [7, pins.BIG_SEED]


def _text(config):
    with open(ROOT / "chipbench" / "configs" / f"{config}.json") as f:
        return f.read()


def test_mixed_fresh_resolves_to_its_parts_files():
    spec = run.load_cell("mixed.fresh")
    assert spec["mix"]["parts"] == {"tpch_sf100": 3, "tpcds_sf100": 1}
    assert [(p["name"], p["benchmark"]) for p in spec["parts"]] == \
        [("tpch_sf100", "tpch"), ("tpcds_sf100", "tpcds")]
    for p in spec["parts"]:
        assert p["cfg_text"] == _text(p["name"])
        assert os.path.basename(train._cache_path(p["cfg_text"])) == \
            pins.CACHE[p["name"]]
    assert spec["cfg"]["model"] == json.loads(_text("tpch_sf100"))["model"]
    for k in run.SERVICE_GROUPS:
        assert spec["cfg"][k] == json.loads(_text("tpch_sf100"))[k], k


@pytest.mark.parametrize("seed", SEEDS)
def test_mixed_fresh_window_splits_306_to_102(seed):
    reqs = run.Cell(run.load_cell("mixed.fresh")).stream(seed, 51.0)
    assert collections.Counter(r.query.benchmark for r in reqs) == \
        {"tpch": 306, "tpcds": 102}
    ds = collections.Counter(r.query.template for r in reqs
                             if r.query.benchmark == "tpcds")
    assert set(ds.values()) == {1} and len(ds) == 102
    assert max(r.query.n_subqs for r in reqs
               if r.query.benchmark == "tpcds") == 48


@pytest.mark.parametrize("seed", SEEDS)
def test_mixed_fresh_check_sample_holds_each_parts_longest(seed):
    cell = run.Cell(run.load_cell("mixed.fresh"))
    # Every request finished (none shed), as in the cell's runs.
    served = pins._served(cell.stream(seed, 51.0), shed_every=10 ** 9)
    got = check.sample(served, run.CHECK_SAMPLE, seed)
    assert len(got) == run.CHECK_SAMPLE
    assert [(s.request.query.benchmark, s.request.query.n_subqs)
            for s in got[:2]] == [("tpcds", 48), ("tpch", 12)]


@pytest.fixture
def stubbed(monkeypatch):
    """The harness's set-up with random models and recording stand-ins for
    the server's classes."""
    import repro.serve
    monkeypatch.setattr(train, "trained_models", pins._random_models)
    monkeypatch.setattr(repro.serve, "TuningService", pins._Tuning)
    monkeypatch.setattr(repro.serve, "RuntimeSession", pins._Session)
    monkeypatch.setattr(repro.serve, "OptimizerServer", pins._Server)
    pins._Recorder.made = []
    return pins._Recorder.made


@pytest.mark.parametrize("cell", ["tpch.fresh", "tpcds.fresh",
                                  "mixed.fresh"])
def test_setup_hands_the_server_each_parts_models(stubbed, cell):
    from repro.core.models.perf_model import PerfModel
    c = run.Cell(run.load_cell(cell))
    c.setup()
    made = dict(stubbed)
    tuning, session = made["_Tuning"]["model"], made["_Session"]
    if len(c.parts) == 1:
        (part,) = c.parts
        assert isinstance(tuning, PerfModel)
        assert tuning is part["models"]["subq"]
        assert session["model_subq"] is part["models"]["subq"]
        assert session["model_qs"] is part["models"]["qs"]
        return
    for part in c.parts:
        b = part["benchmark"]
        assert tuning[b] is part["models"]["subq"]
        assert session["model_subq"][b] is part["models"]["subq"]
        assert session["model_qs"][b] is part["models"]["qs"]
    assert set(tuning) == {"tpch", "tpcds"}


@pytest.mark.parametrize("cell,seed", list(pins.STREAMS))
def test_one_part_streams_are_pinned(cell, seed):
    c = run.Cell(run.load_cell(cell))
    window = c.stream(seed, 51.0)
    warm = c.stream(seed ^ 0x3A3A3A, 51.0, warmup=True)
    assert (pins._digest(window), pins._digest(warm)) == \
        pins.STREAMS[cell, seed]


@pytest.mark.parametrize("cell,seed", list(pins.SAMPLES))
def test_one_part_check_samples_are_pinned(cell, seed):
    served = pins._served(run.Cell(run.load_cell(cell)).stream(
        pins.BIG_SEED, 51.0))
    assert [s.rid for s in check.sample(served, run.CHECK_SAMPLE, seed)] == \
        pins.SAMPLES[cell, seed]


@pytest.mark.parametrize("config", list(pins.CACHE))
def test_one_part_model_cache_paths_are_pinned(config):
    assert os.path.basename(train._cache_path(_text(config))) == \
        pins.CACHE[config]
