"""Readings that set a cell's correctness limits, at the cell's own size:

    python3 bench/control.py --workload <cell> --seconds 5 \\
        --seeds 1 2 3 ... --precision bfloat16 int8 --fault swap_frames

For each seed, one short run of the cell in this process (the program's
reading of each compared number), then each control: the plain reference
computed at a lower precision put in the program's place, on the same
sample, read by the same numbers and judged by the same limits.  Then,
for each seed and each named fault of the model's adapter (``FAULTS``),
one more run with the timed path broken that way: its readings and
whether it came out correct.  Prints one JSON line per run and a
summary: the largest program reading of each number (the lower end of
its limit) and the smallest reading of each control and fault (the
upper end).  Needs the chip, like run.py.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys


def _run(root, workload, seed, seconds, chip_check, patch=None):
    import harness

    held = {}

    def hold(dep):
        held["dep"] = dep
        if patch is not None:
            patch(dep)

    res = harness.run_cell(root, workload, seed, seconds, False,
                           chip_check=chip_check, patch=hold,
                           out=io.StringIO(), err=io.StringIO())
    return res, held["dep"]


def readings(root, workload, seeds, seconds, precisions, faults=(),
             chip_check=True, log=sys.stdout):
    import harness
    from traffic import generator

    rows = []
    for seed in seeds:
        res, dep = _run(root, workload, seed, seconds, chip_check)
        limits = {k: v["limit"] for k, v in res["compared"].items()}
        control = {p: dep.control(generator.rng(seed, "sample"), p)
                   for p in precisions}
        row = {"seed": seed, "correct": res["correct"],
               "program": {k: v["value"]
                           for k, v in res["compared"].items()},
               "control": control,
               "control_correct": {
                   p: all(limits[k] is not None and v <= limits[k]
                          for k, v in c.items())
                   for p, c in control.items()}}
        print(json.dumps(row), file=log, flush=True)
        rows.append(row)
    adapter = harness.load_cell(root, workload).adapter()
    broken = []
    for name in faults:
        for seed in seeds:
            res, _ = _run(root, workload, seed, seconds, chip_check,
                          patch=adapter.FAULTS[name])
            row = {"seed": seed, "fault": name, "correct": res["correct"],
                   "program": {k: v["value"]
                               for k, v in res["compared"].items()}}
            print(json.dumps(row), file=log, flush=True)
            broken.append(row)
    names = rows[0]["program"].keys()
    summary = {"lower": {n: max(r["program"][n] for r in rows)
                         for n in names},
               "upper": {p: {n: min(r["control"][p][n] for r in rows)
                             for n in names}
                         for p in precisions},
               "faults": {f: {"least": {n: min(r["program"][n]
                                             for r in broken
                                             if r["fault"] == f)
                                        for n in names},
                              "correct": sum(r["correct"] for r in broken
                                             if r["fault"] == f)}
                          for f in faults},
               "seeds": len(rows)}
    print(json.dumps({"summary": summary}), file=log, flush=True)
    return rows, broken, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--precision", nargs="*", default=["bfloat16"])
    ap.add_argument("--fault", nargs="*", default=[])
    args = ap.parse_args(argv)
    import harness

    try:
        readings(os.path.dirname(harness.BENCH), args.workload, args.seeds,
                 args.seconds, args.precision, args.fault)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    sys.path[:0] = [here, os.path.join(root, "src")]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    sys.exit(main())
