"""The traffic generator: deterministic per seed, the same work for every
seed, and a fresh mix never repeats a (part, template, variant) within a
run."""
import collections
import json
import os

import numpy as np
import pytest

from chipbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
CB = os.path.dirname(HERE)
BIG_SEED = 2 ** 31 + 12345
# Every cell's mix, by the cell's configuration.
with open(os.path.join(os.path.dirname(CB), "BENCHMARK.json")) as _f:
    MIXES = {w["traffic"]: (w["config"], w["traffic"])
             for w in json.load(_f)["workloads"]}


def _load(mix):
    """The workload group of each part of the mix's configuration (one for
    a configuration without parts), and the mix."""
    config, m = MIXES[mix]
    with open(os.path.join(CB, "configs", config + ".json")) as f:
        cfg = json.load(f)
    wls = {}
    for part in cfg.get("parts", [config]):
        with open(os.path.join(CB, "configs", part + ".json")) as f:
            wls[part] = json.load(f)["workload"]
    if isinstance(m, str):
        with open(os.path.join(CB, "traffic", m + ".json")) as f:
            m = json.load(f)
    return wls, m


def _key(reqs):
    return [(r.query.qid, r.arrival_s) for r in reqs]


@pytest.mark.parametrize("mix", MIXES)
def test_deterministic_per_seed(mix):
    wls, m = _load(mix)
    a = traffic.stream(wls, m, BIG_SEED, 3.0)
    b = traffic.stream(wls, m, BIG_SEED, 3.0)
    assert _key(a) == _key(b)
    assert [r.rid for r in a] == list(range(len(a)))
    assert np.all(np.diff([r.arrival_s for r in a]) >= 0)
    assert a[-1].arrival_s == pytest.approx(len(a) / m["rate_qps"])
    assert len(a) == max(1, round(m["rate_qps"] * 3.0))
    c = traffic.stream(wls, m, BIG_SEED + 1, 3.0)
    assert _key(c) != _key(a)


@pytest.mark.parametrize("mix", MIXES)
def test_same_work_for_every_seed(mix):
    """Seeds reorder one fixed set of templates and gaps."""
    wls, m = _load(mix)
    runs = [traffic.stream(wls, m, s, 3.0) for s in (1, 77, BIG_SEED)]
    templates = [sorted((r.query.benchmark, r.query.template)
                        for r in reqs) for reqs in runs]
    gaps = [sorted(np.diff([0.0] + [r.arrival_s for r in reqs]).round(12))
            for reqs in runs]
    subqs = [sorted(r.query.n_subqs for r in reqs) for reqs in runs]
    assert templates[0] == templates[1] == templates[2]
    assert gaps[0] == gaps[1] == gaps[2]
    assert subqs[0] == subqs[1] == subqs[2]


@pytest.mark.parametrize("mix", [m for m in MIXES if "fresh" in m])
def test_fresh_never_repeats_a_pair(mix):
    wls, m = _load(mix)
    for seed in (5, BIG_SEED):
        warm = traffic.stream(wls, m, seed ^ 0x3A3A3A, 3.0, warmup=True)
        win = traffic.stream(wls, m, seed, 3.0)
        qids = [r.query.qid for r in warm + win]
        assert len(set(qids)) == len(qids)


@pytest.mark.parametrize("mix", MIXES)
def test_fresh_mix_is_uniform_over_templates(mix):
    """Within each part, every template equally often (to one)."""
    wls, m = _load(mix)
    shares = m.get("parts", {p: 1 for p in wls})
    total = sum(shares.values())
    seconds = max(wl["n_templates"] * total / shares[p] for p, wl
                  in wls.items()) / m["rate_qps"] * 2
    reqs = traffic.stream(wls, m, 9, seconds)
    for wl in wls.values():
        counts = collections.Counter(r.query.template for r in reqs
                                     if r.query.benchmark == wl["benchmark"])
        assert set(counts) == set(range(wl["n_templates"]))
        assert max(counts.values()) - min(counts.values()) <= 1
