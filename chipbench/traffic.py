"""The one traffic generator: a stream of timed requests from a mix file.

A mix (``chipbench/traffic/<name>.json``) holds only parameters:

* ``templates``: ``"uniform"`` (every template equally often);
* ``fresh``: every request a (part, template, variant) not sent before in
  the run, warm-up included;
* ``rate_qps``: the offered Poisson arrival rate of the whole stream; a
  stream of ``s`` seconds holds ``n = round(rate_qps * s)`` requests, the
  last arriving at ``n / rate_qps``;
* ``warmup_s``: seconds of the same traffic served before the window;
* ``mix_seed``: fixes the set of parts, templates, variants and gaps;
* ``parts`` (for a composite configuration): ``{"<config>": <integer
  share>, ...}``.  The ``n`` requests split across the parts by share,
  exactly (largest remainder, ties in an order fixed by ``mix_seed``);
  ``templates`` and ``fresh`` apply within each part over its own
  templates.  A mix without ``parts`` serves its configuration's one
  workload.

Every seed gets the same set of (part, template) and inter-arrival gaps,
in an order of its own, so a run's work does not depend on its seed; a
fresh mix also gives each seed its own variants.  Copied in spirit from
the program's ``serving_stream`` and ``ArrivalModel`` (exponential gaps)
and imported from neither.
"""
from __future__ import annotations

import zlib
from typing import Dict, List, Tuple

import numpy as np

from repro.queryengine.workloads import StreamRequest

from .queries import make_query


def _templates(rule: str, n_templates: int, n: int,
               rng: np.random.Generator) -> List[int]:
    """The fixed multiset of templates of ``n`` requests of one workload."""
    if rule == "uniform":
        rest = rng.permutation(n_templates)[:n % n_templates]
        t = np.concatenate([np.tile(np.arange(n_templates), n // n_templates),
                            rest])
        return [int(x) for x in t]
    raise ValueError(f"unknown template mix {rule!r}")


def split(shares: Dict[str, int], n: int, rng: np.random.Generator
          ) -> Dict[str, int]:
    """``n`` requests split by integer shares: each part the floor of its
    quota, the rest one each to the largest remainders, ties in an order
    drawn from ``rng``."""
    if any(not isinstance(s, int) or s < 1 for s in shares.values()):
        raise ValueError(f"shares must be positive integers: {shares}")
    total = sum(shares.values())
    names = list(shares)
    counts = {p: n * shares[p] // total for p in names}
    tie = rng.permutation(len(names))
    order = sorted(range(len(names)),
                   key=lambda i: (-(n * shares[names[i]] % total), tie[i]))
    for i in order[:n - sum(counts.values())]:
        counts[names[i]] += 1
    return counts


def _pairs(mix: dict, workloads: Dict[str, dict], n: int, salt: int
           ) -> List[Tuple[str, int, int]]:
    """The fixed multiset of (part, template, variant) of one stream."""
    if "parts" not in mix:
        if len(workloads) != 1:
            raise ValueError(f"a mix without parts serves one workload, "
                             f"not {sorted(workloads)}")
        (name, wl), = workloads.items()
        rng = np.random.default_rng(np.random.SeedSequence(
            [mix["mix_seed"], salt]))
        return [(name, t, 0) for t in _templates(
            mix["templates"], wl["n_templates"], n, rng)]
    if sorted(mix["parts"]) != sorted(workloads):
        raise ValueError(f"the mix shares among {sorted(mix['parts'])}, "
                         f"the configuration has {sorted(workloads)}")
    counts = split(mix["parts"], n, np.random.default_rng(
        np.random.SeedSequence([mix["mix_seed"], salt, 0x5917])))
    out = []
    for name, k in counts.items():
        rng = np.random.default_rng(np.random.SeedSequence(
            [mix["mix_seed"], salt, zlib.crc32(name.encode())]))
        out += [(name, t, 0) for t in _templates(
            mix["templates"], workloads[name]["n_templates"], k, rng)]
    return out


def stream(workloads: Dict[str, dict], mix: dict, seed: int, seconds: float,
           *, warmup: bool = False) -> List[StreamRequest]:
    """The window's stream (or, with ``warmup``, the warm-up's) of a run;
    ``workloads`` maps each part's name to its ``workload`` group."""
    span = mix["warmup_s"] if warmup else seconds
    n = max(1, int(round(mix["rate_qps"] * span)))
    salt = 1 if warmup else 0
    pairs = _pairs(mix, workloads, n, salt)
    # Poisson arrivals given their count: exponential gaps scaled to end at
    # n / rate, so every stream offers exactly the mix's rate.
    gaps = np.random.default_rng(np.random.SeedSequence(
        [mix["mix_seed"], salt, 0xA221])).exponential(1.0, n)
    gaps *= n / mix["rate_qps"] / gaps.sum()
    order = np.random.default_rng(np.random.SeedSequence(
        [seed, salt, 0x5EED]))
    pairs = [pairs[i] for i in order.permutation(n)]
    arrivals = np.cumsum(order.permutation(gaps))
    if mix["fresh"]:
        # Variant ids of their own per seed; even for warm-up, odd for
        # the window, so no (part, template, variant) repeats within a run.
        base = 4096 + (seed % (1 << 20)) * (1 << 14)
        pairs = [(p, t, base + 2 * i + (0 if warmup else 1))
                 for i, (p, t, _) in enumerate(pairs)]
    built = {}
    out = []
    for i, (pair, at) in enumerate(zip(pairs, arrivals)):
        if pair not in built:
            built[pair] = make_query(workloads[pair[0]], *pair[1:])
        out.append(StreamRequest(rid=i, query=built[pair],
                                 arrival_s=float(at)))
    return out
