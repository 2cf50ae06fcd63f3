"""The one traffic generator: a stream of timed requests from a mix file.

A mix (``chipbench/traffic/<name>.json``) holds only parameters:

* ``templates``: ``"uniform"`` (every template equally often);
* ``fresh``: every request a (template, variant) pair not sent before in
  the run, warm-up included;
* ``rate_qps``: the offered Poisson arrival rate; a stream of ``s``
  seconds holds ``n = round(rate_qps * s)`` requests, the last arriving at
  ``n / rate_qps``;
* ``warmup_s``: seconds of the same traffic served before the window;
* ``mix_seed``: fixes the set of templates, variants and gaps.

Every seed gets the same set of templates and inter-arrival gaps, in an
order of its own, so a run's work does not depend on its seed; a fresh mix
also gives each seed its own variants.  Copied in spirit from the
program's ``serving_stream`` and ``ArrivalModel`` (exponential gaps) and
imported from neither.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from repro.queryengine.workloads import StreamRequest

from .queries import make_query


def _pairs(mix: dict, n_templates: int, n: int, salt: int
           ) -> List[Tuple[int, int]]:
    """The fixed multiset of (template, variant) of one stream."""
    rng = np.random.default_rng(np.random.SeedSequence([mix["mix_seed"],
                                                        salt]))
    if mix["templates"] == "uniform":
        rest = rng.permutation(n_templates)[:n % n_templates]
        t = np.concatenate([np.tile(np.arange(n_templates), n // n_templates),
                            rest])
        return [(int(x), 0) for x in t]
    raise ValueError(f"unknown template mix {mix['templates']!r}")


def stream(wl: dict, mix: dict, seed: int, seconds: float, *,
           warmup: bool = False) -> List[StreamRequest]:
    """The window's stream (or, with ``warmup``, the warm-up's) of a run."""
    span = mix["warmup_s"] if warmup else seconds
    n = max(1, int(round(mix["rate_qps"] * span)))
    salt = 1 if warmup else 0
    pairs = _pairs(mix, wl["n_templates"], n, salt)
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
        # the window, so no pair repeats within a run.
        base = 4096 + (seed % (1 << 20)) * (1 << 14)
        pairs = [(t, base + 2 * i + (0 if warmup else 1))
                 for i, (t, _) in enumerate(pairs)]
    built = {}
    out = []
    for i, (pair, at) in enumerate(zip(pairs, arrivals)):
        if pair not in built:
            built[pair] = make_query(wl, *pair)
        out.append(StreamRequest(rid=i, query=built[pair],
                                 arrival_s=float(at)))
    return out
