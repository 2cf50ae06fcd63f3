"""Composite configurations: one server on one chip for several benchmarks.

A configuration file with ``parts`` names other configurations; each part
brings its workload, model and training in its own file (so its trained
models are the ones its own cell cached), the composite file sets the one
service, and a mix with ``parts`` shares the stream among them.  On the
CPU, with random parameters and no training:

* a two-part composite (``tpch_sf100`` : ``tpcds_sf100`` at 3 : 1) resolves,
  each part read byte for byte from its own file, and one that cannot be
  served by one set of FLOP readers is refused;
* its stream meets the shares exactly, is deterministic per seed, gives
  every seed the same work, is uniform within each part and repeats nothing;
* its set-up hands the server ``{benchmark: PerfModel}`` maps, where a
  one-part configuration hands it single models;
* its check holds each part's longest request, reads each part with its
  own models, and fails when one part's head rows are corrupted;
* one-part configurations give the streams, check samples and model cache
  paths that the harness gave before composites existed (pinned digests).
"""
import collections
import hashlib
import json
import os
import types

import numpy as np
import pytest

from chipbench import check, run, traffic, train

HERE = os.path.dirname(os.path.abspath(__file__))
CB = os.path.dirname(HERE)
ROOT = os.path.dirname(CB)
PARTS = {"tpch_sf100": 3, "tpcds_sf100": 1}
BIG_SEED = 2 ** 31 + 12345
MIX = {"templates": "uniform", "fresh": True, "rate_qps": 8.0,
       "warmup_s": 4.0, "mix_seed": 1, "parts": PARTS}


def _text(name):
    with open(os.path.join(CB, "configs", name + ".json")) as f:
        return f.read()


def _composite(**change):
    """The composite file: the one service, and its parts by name."""
    part = json.loads(_text("tpch_sf100"))
    cfg = {"name": "mixed_sf100", "source": "the parts' own sources",
           "deployment": "one optimizer service for a TPC-H and a TPC-DS "
                         "warehouse on one chip",
           "parts": list(PARTS),
           **{k: part[k] for k in run.SERVICE_GROUPS},
           "reduced": [], "assumed": ["the service groups of tpch_sf100"]}
    cfg.update(change)
    return {k: v for k, v in cfg.items() if v is not None}


def _root(tmp, composite=None, parts=None, mix=None):
    """A checkout whose BENCHMARK.json adds the ``mixed.fresh`` cell; its
    part files are the real ones unless ``parts`` gives texts."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "mixed_sf100", "source": "x",
                             "file": "chipbench/configs/mixed_sf100.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "mixed.fresh", "config": "mixed_sf100",
                               "traffic": "mixed_fresh", "chips": 1,
                               "why": "x"})
    for sub in ("configs", "traffic"):
        os.makedirs(tmp / "chipbench" / sub, exist_ok=True)
    for name in PARTS:
        (tmp / "chipbench" / "configs" / f"{name}.json").write_text(
            (parts or {}).get(name, _text(name)))
    (tmp / "chipbench" / "configs" / "mixed_sf100.json").write_text(
        json.dumps(composite or _composite()))
    (tmp / "chipbench" / "traffic" / "mixed_fresh.json").write_text(
        json.dumps(mix or MIX))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def root(tmp_path):
    return _root(tmp_path)


# -- the configuration --------------------------------------------------------

def test_composite_resolves_to_its_parts_files(root):
    spec = run.load_cell("mixed.fresh", str(root))
    assert [p["name"] for p in spec["parts"]] == list(PARTS)
    assert [p["benchmark"] for p in spec["parts"]] == ["tpch", "tpcds"]
    for p in spec["parts"]:
        assert p["cfg_text"] == _text(p["name"])
        assert train._cache_path(p["cfg_text"]) == \
            train._cache_path(_text(p["name"]))
    cfg = spec["cfg"]
    assert cfg["model"] == json.loads(_text("tpch_sf100"))["model"]
    assert "workload" not in cfg and cfg["parts"] == list(PARTS)
    for k in run.SERVICE_GROUPS:
        assert cfg[k] == _composite()[k]


def test_one_part_configuration_is_its_own_part():
    spec = run.load_cell("tpch.fresh")
    assert spec["cfg_text"] == _text("tpch_sf100")
    assert spec["cfg"] == json.loads(_text("tpch_sf100"))
    assert [(p["name"], p["cfg_text"]) for p in spec["parts"]] == \
        [("tpch_sf100", _text("tpch_sf100"))]


def test_mismatched_model_groups_are_refused(tmp_path):
    other = json.loads(_text("tpcds_sf100"))
    other["model"] = dict(other["model"], hidden=[64, 48])
    root = _root(tmp_path, parts={"tpcds_sf100": json.dumps(other)})
    with pytest.raises(SystemExit, match="model groups differ"):
        run.load_cell("mixed.fresh", str(root))


@pytest.mark.parametrize("composite,mix,says", [
    (_composite(parts=["tpch_sf100"]), None, "two parts or more"),
    (_composite(parts=["tpch_sf100", "tpch_sf100"]), None, "one benchmark"),
    (_composite(parts=["tpch_sf100", "nowhere"]), None, "no configuration"),
    (_composite(model={"gtn": {}}), None, "holds"),
    (_composite(cost=None), None, "lacks"),
    (None, dict(MIX, parts={"tpch_sf100": 1}), "shares"),
    (None, {k: v for k, v in MIX.items() if k != "parts"}, "shares"),
], ids=["one_part", "one_part_twice", "unknown_part", "own_model",
        "no_cost", "mix_part_missing", "mix_without_parts"])
def test_malformed_composites_are_refused(tmp_path, composite, mix, says):
    root = _root(tmp_path, composite=composite, mix=mix)
    with pytest.raises(SystemExit, match=says):
        run.load_cell("mixed.fresh", str(root))


# -- the stream ---------------------------------------------------------------

def _workloads():
    return {p: json.loads(_text(p))["workload"] for p in PARTS}


@pytest.mark.parametrize("n", [1, 2, 3, 5, 10, 408, 409])
def test_split_meets_the_shares_exactly(n):
    rng = np.random.default_rng(3)
    got = traffic.split({"a": 3, "b": 1, "c": 2}, n, rng)
    assert sum(got.values()) == n
    for p, s in (("a", 3), ("b", 1), ("c", 2)):
        assert n * s // 6 <= got[p] <= -(-n * s // 6)
    assert traffic.split({"a": 3, "b": 1, "c": 2}, n,
                         np.random.default_rng(3)) == got


def test_split_refuses_shares_that_are_not_positive_integers():
    with pytest.raises(ValueError):
        traffic.split({"a": 1.5, "b": 1}, 4, np.random.default_rng(0))


def _parts_of(reqs):
    return collections.Counter(r.query.benchmark for r in reqs)


@pytest.mark.parametrize("seconds", [51.0, 3.0, 0.25])
def test_stream_meets_its_shares(seconds):
    reqs = traffic.stream(_workloads(), MIX, BIG_SEED, seconds)
    n = max(1, round(MIX["rate_qps"] * seconds))
    assert len(reqs) == n
    assert reqs[-1].arrival_s == pytest.approx(n / MIX["rate_qps"])
    got = _parts_of(reqs)
    assert sum(got.values()) == n
    for bench, share in (("tpch", 3), ("tpcds", 1)):
        assert n * share // 4 <= got[bench] <= -(-n * share // 4)
    if seconds == 51.0:
        assert got == {"tpch": 306, "tpcds": 102}


def _key(reqs):
    return [(r.query.qid, r.arrival_s) for r in reqs]


def test_stream_is_deterministic_per_seed_and_the_same_work_for_every_seed():
    wls = _workloads()
    a = traffic.stream(wls, MIX, BIG_SEED, 20.0)
    assert _key(a) == _key(traffic.stream(wls, MIX, BIG_SEED, 20.0))
    runs = [traffic.stream(wls, MIX, s, 20.0) for s in (1, 77, BIG_SEED)]
    assert len({tuple(_key(r)) for r in runs}) == 3
    work = [sorted((r.query.benchmark, r.query.template, r.query.n_subqs)
                   for r in reqs) for reqs in runs]
    gaps = [sorted(np.diff([0.0] + [r.arrival_s for r in reqs]).round(12))
            for reqs in runs]
    assert work[0] == work[1] == work[2]
    assert gaps[0] == gaps[1] == gaps[2]


def test_stream_is_uniform_within_each_part():
    wls = _workloads()
    # Two rounds of TPC-DS's 102 templates at a quarter of the stream.
    reqs = traffic.stream(wls, MIX, 9, 102 * 4 * 2 / MIX["rate_qps"])
    for wl in wls.values():
        counts = collections.Counter(r.query.template for r in reqs
                                     if r.query.benchmark == wl["benchmark"])
        assert set(counts) == set(range(wl["n_templates"]))
        assert max(counts.values()) - min(counts.values()) <= 1


def test_stream_repeats_nothing_warm_up_included():
    wls = _workloads()
    for seed in (5, BIG_SEED):
        warm = traffic.stream(wls, MIX, seed ^ 0x3A3A3A, 20.0, warmup=True)
        win = traffic.stream(wls, MIX, seed, 20.0)
        qids = [r.query.qid for r in warm + win]
        assert len(set(qids)) == len(qids)
        assert set(_parts_of(warm)) == set(_parts_of(win)) == {"tpch",
                                                               "tpcds"}


# -- set-up and warm-up ------------------------------------------------------

def _random_models(cfg, cfg_text, log):
    """``train.trained_models`` with seeded random parameters, no training."""
    import jax
    mc = cfg["model"]
    seed = int(hashlib.sha256(cfg_text.encode()).hexdigest()[:6], 16)
    params = {kind: jax.tree_util.tree_map(np.asarray, train.init_params(
        jax.random.PRNGKey(seed + i), mc["gtn"], mc["hidden"],
        mc["theta_dim"][kind], mc["n_targets"]))
        for i, kind in enumerate(train.KINDS)}
    stats = {kind: np.stack([np.zeros(mc["n_targets"]),
                             np.ones(mc["n_targets"])])
             for kind in train.KINDS}
    return params, stats, {"traces": 0.0, "training": 0.0}


class _Recorder:
    """Stands in for a server class and keeps what it was given."""
    made = []

    def __init__(self, **kw):
        self.kw = kw
        self.made.append((type(self).__name__, kw))


class _Tuning(_Recorder):
    pass


class _Session(_Recorder):
    pass


class _Server(_Recorder):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.tuning, self.session = kw["tuning"], kw["session"]
        self.streams = []

    def serve(self, stream):
        self.streams.append(stream)
        return []


@pytest.fixture
def stubbed(monkeypatch):
    import repro.serve
    monkeypatch.setattr(train, "trained_models", _random_models)
    monkeypatch.setattr(repro.serve, "TuningService", _Tuning)
    monkeypatch.setattr(repro.serve, "RuntimeSession", _Session)
    monkeypatch.setattr(repro.serve, "OptimizerServer", _Server)
    _Recorder.made = []
    return _Recorder.made


def test_composite_setup_passes_benchmark_maps(root, stubbed):
    from repro.core.models.perf_model import PerfModel
    cell = run.Cell(run.load_cell("mixed.fresh", str(root)))
    cell.setup()
    made = dict(stubbed)
    tuning, session = made["_Tuning"], made["_Session"]
    assert set(tuning["model"]) == {"tpch", "tpcds"}
    for bench, part in zip(("tpch", "tpcds"), cell.parts):
        assert tuning["model"][bench] is part["models"]["subq"]
        assert session["model_subq"][bench] is part["models"]["subq"]
        assert session["model_qs"][bench] is part["models"]["qs"]
        assert isinstance(part["models"]["qs"], PerfModel)
        assert part["models"]["qs"].cfg.kind == "qs"
    assert tuning["model"]["tpch"] is not tuning["model"]["tpcds"]
    assert tuning["cfg"] == cell.hcfg
    assert session["weights"] == tuple(_composite()["weights"])


def test_one_part_setup_passes_single_models(stubbed):
    from repro.core.models.perf_model import PerfModel
    cell = run.Cell(run.load_cell("tpch.fresh"))
    cell.setup()
    made = dict(stubbed)
    assert isinstance(made["_Tuning"]["model"], PerfModel)
    assert made["_Tuning"]["model"] is cell.models["subq"]
    assert made["_Session"]["model_subq"] is cell.models["subq"]
    assert made["_Session"]["model_qs"] is cell.models["qs"]


def test_warm_up_runs_every_bucket_through_every_parts_models(
        root, stubbed, monkeypatch):
    from repro.core.models import perf_model
    cell = run.Cell(run.load_cell("mixed.fresh", str(root)))
    cell.setup()
    rows = collections.defaultdict(list)
    for p in cell.parts:
        for kind, m in p["models"].items():
            monkeypatch.setattr(m, "predict_rows",
                                lambda e, t, d, k=(p["name"], kind):
                                rows[k].append(len(e)))
    cell.warm(BIG_SEED, 3.0)
    (warm,) = cell.server.streams
    assert set(_parts_of(warm)) == {"tpch", "tpcds"}
    b, want = perf_model.MIN_DISPATCH_ROWS, []
    while b <= perf_model._head_max_bucket():
        want.append(b)
        b *= 2
    assert dict(rows) == {(p, k): want for p in PARTS for k in train.KINDS}


def test_a_warm_up_the_program_cannot_serve_stops_the_run(root, stubbed):
    def refuse(stream):
        raise AttributeError("'dict' object has no attribute 'embed_many'")
    cell = run.Cell(run.load_cell("mixed.fresh", str(root)))
    cell.setup()
    cell.server.serve = refuse
    with pytest.raises(SystemExit, match="could not serve the warm-up of "
                       "mixed.fresh.*AttributeError"):
        cell.warm(BIG_SEED, 3.0)


# -- the check ----------------------------------------------------------------

def _served(reqs, shed_every=7):
    return [types.SimpleNamespace(
        rid=r.rid, request=r, ct=object(), result=object(),
        status="shed" if r.rid % shed_every == 3 else "served") for r in reqs]


def test_check_sample_holds_each_parts_longest_request():
    served = _served(traffic.stream(_workloads(), MIX, BIG_SEED, 51.0))
    done = [s for s in served if s.status == "served"]
    for seed in (5, BIG_SEED):
        got = check.sample(served, run.CHECK_SAMPLE, seed)
        assert len(got) == run.CHECK_SAMPLE
        assert len({s.rid for s in got}) == run.CHECK_SAMPLE
        assert all(s.status == "served" for s in got)
        for bench, i in (("tpcds", 0), ("tpch", 1)):
            mine = [s for s in done if s.request.query.benchmark == bench]
            longest = max(s.request.query.n_subqs for s in mine)
            assert got[i].request.query.benchmark == bench
            assert got[i].request.query.n_subqs == longest
        assert got == check.sample(served, run.CHECK_SAMPLE, seed)


# Per part: (template, arrival).  Small templates only: the eager reference
# compiles its operations once per query size.
WINDOW = {"tpch": [(0, 0.0), (3, 0.02), (5, 0.1)],
          "tpcds": [(3, 0.01), (0, 0.05)]}


def _corrupt_head(model):
    orig = model._head
    model._head = lambda p, e, t, d: orig(p, e, t, d) * 1.01


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("composite")
    hmooc = dict(json.loads(_text("tpch_sf100"))["hmooc"], n_c_init=16,
                 n_p_pool=64, n_c_enrich=16, max_bank=16)
    return _root(tmp, composite=_composite(hmooc=hmooc))


def _window(cell, corrupt=None, seed=2 ** 31 + 31):
    """What a server that routes by benchmark would produce: each part's
    requests served with its own models (one server per part, since the
    program holds one model pair), with the heads recorded as
    ``Cell.window`` records them."""
    from chipbench.probes import HeadRecorder
    from repro.queryengine.workloads import StreamRequest
    from repro.serve import (OptimizerServer, RuntimeSession, ServerConfig,
                             ServiceTimeModel, TuningService)
    from chipbench.queries import make_query
    clock = ServiceTimeModel(flush_points=((1, 0.05), (8, 0.2)), round_s=0.01)
    served, heads, rid = [], {}, 0
    for j, p in enumerate(cell.parts):
        models = p["models"]
        if p["name"] == corrupt:
            _corrupt_head(models["subq"])
        heads[p["name"]] = {k: HeadRecorder(m, seed + 2 * j + i)
                            for i, (k, m) in enumerate(models.items())}
        reqs = []
        for t, at in WINDOW[p["benchmark"]]:
            reqs.append(StreamRequest(rid=rid, query=make_query(
                p["cfg"]["workload"], t, 9000 + rid), arrival_s=at))
            rid += 1
        server = OptimizerServer(
            config=ServerConfig(**dict(cell.cfg["server"], clock=clock)),
            tuning=TuningService(model=models["subq"], cfg=cell.hcfg),
            session=RuntimeSession(model_subq=models["subq"],
                                   model_qs=models["qs"],
                                   weights=cell.weights))
        try:
            served += server.serve(reqs)
        finally:
            for h in heads[p["name"]].values():
                h.remove()
    return {"served": served, "heads": heads}


@pytest.mark.parametrize("corrupt", [None, "tpch_sf100", "tpcds_sf100"],
                         ids=["sound", "tpch_heads", "tpcds_heads"])
def test_check_reads_each_part_and_fails_one_parts_corrupted_heads(
        tiny_root, monkeypatch, corrupt):
    monkeypatch.setattr(train, "trained_models", _random_models)
    cell = run.Cell(run.load_cell("mixed.fresh", str(tiny_root)))
    cell.setup()
    seed = 2 ** 31 + 31
    obs = _window(cell, corrupt, seed)
    assert [s.status for s in obs["served"]] == ["served"] * 5
    readings = run.check_run(cell, obs, seed)
    ok, checks = check.verdict(readings)
    if corrupt is None:
        assert ok, checks
        sampled = check.sample(obs["served"], run.CHECK_SAMPLE, seed)
        heads = run.head_rows(obs)
        # Each reading is the largest of the parts' own readings.
        for name, got in run.gaps_by_reference(cell, sampled,
                                               heads).items():
            for p in cell.parts:
                mine = [s for s in sampled
                        if s.request.query.benchmark == p["benchmark"]]
                own = check.model_gaps(mine, p["models"], heads[p["name"]],
                                       check.references(p["params"])[name],
                                       p["stats"], cell.cfg)
                assert all(own[k] <= got[k] for k in own), (name, p["name"])
        # The precision control fails as it does for one part.
        high = run.gaps_by_reference(cell, sampled, heads, control="high")
        assert not check.verdict(dict(high[check.REFERENCE],
                                      sequential_diffs=0))[0]
    else:
        assert not ok, checks
        assert readings["head_gap"] > check.LIMITS["head_gap"]
        assert readings["sequential_diffs"] > 0


# -- one part: what the harness gave before composites (pinned) --------------

# sha256 of [[qid, float.hex(arrival)], ...] of each stream, first 16 digits.
STREAMS = {
    ("tpch.fresh", 7): ("fdae2d7eccc40d1c", "c7cc044f9d12394a"),
    ("tpch.fresh", BIG_SEED): ("6e530b4a8b11fbba", "b8a8687e98aeae61"),
    ("tpcds.fresh", 7): ("2fd761cf53d3a1a9", "32d11967248286f7"),
    ("tpcds.fresh", BIG_SEED): ("664e10813439e3d3", "af30f52ba628c728"),
}
SAMPLES = {
    ("tpch.fresh", 5): [1, 34, 35, 62, 75, 81, 83, 85, 113, 126, 144, 156,
                        158, 162, 170, 173, 186, 270, 277, 336, 355, 372, 399,
                        403],
    ("tpch.fresh", 2 ** 31 + 99): [1, 4, 27, 42, 49, 60, 81, 85, 100, 104,
                                   105, 124, 147, 153, 162, 210, 215, 244,
                                   250, 263, 330, 334, 384, 406],
    ("tpcds.fresh", 5): [36, 15, 16, 29, 34, 39, 40, 54, 63, 68, 76, 79, 81,
                         82, 89, 92, 132, 134, 168, 175, 183, 189, 191, 202],
    ("tpcds.fresh", 2 ** 31 + 99): [36, 1, 12, 13, 20, 23, 28, 39, 41, 50,
                                    51, 58, 69, 71, 78, 103, 106, 114, 123,
                                    126, 159, 163, 182, 201],
}
CACHE = {"tpch_sf100": "46ce4f02818f77d3.npz",
         "tpcds_sf100": "b07a4af8e1af3d07.npz"}


def _digest(reqs):
    return hashlib.sha256(json.dumps(
        [[r.query.qid, float.hex(r.arrival_s)] for r in reqs]).encode()
    ).hexdigest()[:16]


@pytest.mark.parametrize("cell,seed", list(STREAMS))
def test_one_part_streams_are_pinned(cell, seed):
    c = run.Cell(run.load_cell(cell))
    window = c.stream(seed, 51.0)
    warm = c.stream(seed ^ 0x3A3A3A, 51.0, warmup=True)
    assert (_digest(window), _digest(warm)) == STREAMS[cell, seed]


@pytest.mark.parametrize("cell,seed", list(SAMPLES))
def test_one_part_check_samples_are_pinned(cell, seed):
    served = _served(run.Cell(run.load_cell(cell)).stream(BIG_SEED, 51.0))
    assert [s.rid for s in check.sample(served, run.CHECK_SAMPLE, seed)] == \
        SAMPLES[cell, seed]


@pytest.mark.parametrize("config", list(CACHE))
def test_one_part_model_cache_paths_are_pinned(config):
    assert os.path.basename(train._cache_path(_text(config))) == CACHE[config]
