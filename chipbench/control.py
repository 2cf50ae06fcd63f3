#!/usr/bin/env python3
"""The check's readings of sound runs and of the precision control.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

One process, one set-up; for each seed a window of the cell at its own
load, then the check's numbers twice: for what the program produced
(sound readings, the lower end of each limit), and for the plain reference
put in the program's place and computed with three bfloat16 products per
matrix product -- ``Precision.HIGH``, the nearest precision below the
configuration's float32 at ``HIGHEST`` (the control, the upper end).  One
bfloat16 product (``DEFAULT``) is read beside it.  Each is read against
the reference the check compares with, and against the other one of
``check.references`` (keys ``<name>@<reference>``).  One JSON line per
seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import run  # noqa: E402


CONTROLS = ("high", "default")


def read_seed(cell, seed: int, seconds: float) -> dict:
    """One window of the cell; the check's readings of the program and of
    each control put in its place, and each one's verdict.  Each part of a
    composite configuration is read with its own models; each reading is
    the largest over the parts."""
    from chipbench import check

    obs = cell.window(cell.stream(seed, seconds), seed, None)
    prog = run.check_run(cell, obs, seed)
    sampled = check.sample(obs["served"], run.CHECK_SAMPLE, seed)
    heads = run.head_rows(obs)
    out = {"seed": seed, "n": len(obs["served"]), "program": prog}
    program = run.gaps_by_reference(cell, sampled, heads)
    controls = {name: run.gaps_by_reference(cell, sampled, heads,
                                            control=name)
                for name in CONTROLS}
    for ref_name in program:
        if ref_name != check.REFERENCE:
            out[f"program@{ref_name}"] = dict(
                program[ref_name], sequential_diffs=prog["sequential_diffs"])
        for name in CONTROLS:
            key = name if ref_name == check.REFERENCE \
                else f"{name}@{ref_name}"
            out[key] = dict(controls[name][ref_name],
                            sequential_diffs=prog["sequential_diffs"])
    out["correct"] = {k: check.verdict(out[k])[0]
                      for k in ("program", *CONTROLS)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.require_chip(spec["cell"]["chips"])
    run.setup_compile_cache()
    cell = run.Cell(spec)
    cell.setup()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        if i == 0:
            cell.warm(seed, args.seconds)
        print(json.dumps(read_seed(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
