"""TPC-H- and TPC-DS-shaped query templates, built from a configuration file.

A copy of the template generator of ``repro.queryengine.workloads``
(``make_query`` and ``_template_tables``) and of ``plan.cbo_estimate``,
kept here so that a change to the program cannot change the queries the
benchmark sends.  The catalog, the fact tables, the predicate vocabulary
and the rule that sizes a template are read from the configuration's
``workload`` group.  Only the program's plain data classes (``Operator``,
``SubQ``, ``Query``) are imported: they are the request type the server
takes.
"""
from __future__ import annotations

import math
import zlib
from typing import List, Tuple

import numpy as np

from repro.queryengine.plan import Operator, Query, SubQ


def cbo_estimate(true_value: float, depth: int, rng: np.random.Generator,
                 sigma0: float = 0.25) -> float:
    """Log-normal CBO estimate whose spread grows with depth."""
    sigma = sigma0 * (1.0 + 0.6 * depth)
    return max(1.0, true_value * math.exp(rng.normal(0.0, sigma)))


def _n_tables(rule: dict, rng: np.random.Generator) -> int:
    if rule["kind"] == "uniform_int":
        return int(rng.integers(rule["lo"], rule["hi"]))
    if rule["kind"] == "geometric":
        return int(np.clip(rng.geometric(rule["p"]) + rule["offset"],
                           rule["lo"], rule["hi"]))
    raise ValueError(f"unknown n_tables rule {rule['kind']!r}")


def _template_tables(wl: dict, rng: np.random.Generator) -> List[str]:
    facts = wl["facts"]
    dims = [n for n in wl["catalog"] if n not in facts]
    n_tables = _n_tables(wl["n_tables"], rng)
    n_facts = min(1 + int(rng.random() < 0.3) + int(rng.random() < 0.15),
                  n_tables, len(facts))
    chosen = list(rng.choice(facts, size=n_facts, replace=False))
    n_dims = n_tables - n_facts
    if n_dims > 0:
        chosen += list(rng.choice(dims, size=n_dims,
                                  replace=n_dims > len(dims)))
    return chosen


def make_query(wl: dict, template: int, variant: int) -> Query:
    """One query: the template fixes the tables and the join tree, the
    variant perturbs selectivities and fan-outs, and the CBO error is fixed
    per (template, variant)."""
    bench = wl["benchmark"]
    seed = wl["template_seed"]
    tag = zlib.crc32(bench.encode()) & 0xFFFF
    srng = np.random.default_rng(np.random.SeedSequence([seed, tag, template]))
    tables = _template_tables(wl, srng)
    vrng = np.random.default_rng(
        np.random.SeedSequence([seed, tag, template, 1000 + variant]))
    erng = np.random.default_rng(
        np.random.SeedSequence([seed, tag, template, 7777 + variant]))
    cat = {k: (float(v[0]), float(v[1])) for k, v in wl["catalog"].items()}
    vocab = wl["pred_vocab"]
    ops: List[Operator] = []
    subqs: List[SubQ] = []

    def new_op(op_type, children, rows, bys, est_rows, est_bytes, toks=()):
        ops.append(Operator(len(ops), op_type, children, rows, bys,
                            est_rows, est_bytes, toks))
        return len(ops) - 1

    def pred(k: int = 3) -> Tuple[str, ...]:
        return tuple(srng.choice(vocab, size=k))

    frontier = []   # (sq_id, rows, bytes, est_rows, est_bytes, width)
    for t_name in tables:
        t_rows, t_width = cat[t_name]
        t_bytes = t_rows * t_width
        sel_base = float(np.exp(srng.uniform(np.log(2e-3), np.log(0.6))))
        sel = float(np.clip(sel_base * np.exp(vrng.normal(0, 0.5)), 1e-5, 1.0))
        proj = float(srng.uniform(0.25, 0.9))
        rows = max(1.0, t_rows * sel)
        width = t_width * proj
        bys = rows * width
        est_rows = cbo_estimate(rows, 0, erng)
        est_bytes = est_rows * width
        o_scan = new_op("scan", [], t_rows, t_bytes, t_rows, t_bytes,
                        (t_name,))
        o_fil = new_op("filter", [o_scan], rows, rows * t_width,
                       est_rows, est_rows * t_width, pred())
        o_prj = new_op("project", [o_fil], rows, bys, est_rows, est_bytes,
                       pred(2))
        sq = SubQ(sq_id=len(subqs), op_ids=[o_scan, o_fil, o_prj],
                  children=[], kind="scan", root_op=o_prj, table=t_name,
                  input_rows=(t_rows,), input_bytes=(t_bytes,),
                  est_input_rows=(t_rows,), est_input_bytes=(t_bytes,),
                  out_rows=rows, out_bytes=bys, est_out_rows=est_rows,
                  est_out_bytes=est_bytes,
                  cpu_weight=float(srng.uniform(0.6, 1.2)),
                  skew=float(srng.beta(1.2, 4.0)), depth=0)
        subqs.append(sq)
        frontier.append((sq.sq_id, rows, bys, est_rows, est_bytes, width))

    srng2 = np.random.default_rng(
        np.random.SeedSequence([seed, tag, template, 5]))
    depth = 0
    while len(frontier) > 1:
        depth += 1
        frontier.sort(key=lambda f: -f[1])
        j = int(srng2.integers(1, len(frontier)))
        (sq_l, r_l, b_l, er_l, eb_l, w_l) = frontier.pop(j)
        (sq_r, r_r, b_r, er_r, eb_r, w_r) = frontier.pop(0)
        fan_base = float(np.exp(srng2.uniform(np.log(0.05), np.log(2.5))))
        fan = float(np.clip(fan_base * np.exp(vrng.normal(0, 0.4)), 1e-4, 8.0))
        rows = max(1.0, fan * max(r_l, r_r))
        width = (w_l + w_r) * float(srng2.uniform(0.4, 0.8))
        bys = rows * width
        est_rows = cbo_estimate(rows, depth, erng)
        est_bytes = est_rows * width
        o_join = new_op("join", [subqs[sq_l].root_op, subqs[sq_r].root_op],
                        rows, bys, est_rows, est_bytes, pred())
        members = [o_join]
        root = o_join
        if srng2.random() < 0.5:
            root = new_op("project", [o_join], rows, bys * 0.9,
                          est_rows, est_bytes * 0.9, pred(2))
            members.append(root)
            bys *= 0.9
            est_bytes *= 0.9
        sq = SubQ(sq_id=len(subqs), op_ids=members, children=[sq_l, sq_r],
                  kind="join", root_op=root,
                  input_rows=(r_l, r_r), input_bytes=(b_l, b_r),
                  est_input_rows=(er_l, er_r), est_input_bytes=(eb_l, eb_r),
                  out_rows=rows, out_bytes=bys, est_out_rows=est_rows,
                  est_out_bytes=est_bytes,
                  cpu_weight=float(srng2.uniform(1.0, 2.0)),
                  skew=float(srng2.beta(1.5, 3.0)), depth=depth)
        subqs.append(sq)
        frontier.append((sq.sq_id, rows, bys, est_rows, est_bytes, width))

    (sq_top, r_t, b_t, er_t, eb_t, w_t) = frontier[0]
    red = float(np.exp(srng2.uniform(np.log(1e-4), np.log(0.2))))
    rows = max(1.0, r_t * red)
    bys = rows * w_t * 0.5
    est_rows = cbo_estimate(rows, depth + 1, erng)
    est_bytes = est_rows * w_t * 0.5
    o_agg = new_op("agg", [subqs[sq_top].root_op], rows, bys, est_rows,
                   est_bytes, pred())
    members = [o_agg]
    root = o_agg
    if srng2.random() < 0.5:
        root = new_op("sort", [o_agg], rows, bys, est_rows, est_bytes,
                      pred(1))
        members.append(root)
    subqs.append(SubQ(sq_id=len(subqs), op_ids=members, children=[sq_top],
                      kind="agg", root_op=root,
                      input_rows=(r_t,), input_bytes=(b_t,),
                      est_input_rows=(er_t,), est_input_bytes=(eb_t,),
                      out_rows=rows, out_bytes=bys, est_out_rows=est_rows,
                      est_out_bytes=est_bytes,
                      cpu_weight=float(srng2.uniform(1.0, 1.8)),
                      skew=float(srng2.beta(1.2, 5.0)), depth=depth + 1))
    return Query(qid=f"{bench}-t{template:03d}-v{variant}", ops=ops,
                 subqs=subqs, benchmark=bench, template=template)
