#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <cell> --control-seeds 1 2 3 \
        --seeds 4 5 6 --seconds 10

For each seed, in one process: one run of the cell (its traffic at its
load, a short window), then the check on the program; for each of
``--control-seeds`` also on the control, the reference computed with fp8
weights put in the program's place in every window of the same sample,
judged by the same comparison.  Prints one JSON line per seed with each
compared number, and whether the control came out as not correct; then,
per number, the largest of the program's readings (the lower reading,
over a dozen seeds or more) and the smallest of the control's (the upper
reading).
"""

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import argparse  # noqa: E402
import json  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    from bench import harness as H
    from bench import spec as S
    H.configure_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("control: needs a TPU")
    cell = S.resolve_cell(args.workload)
    rows = []
    for seed in args.control_seeds + args.seeds:
        control = seed in args.control_seeds
        res = H.run_cell(cell, seed, args.seconds, False,
                         t_start=time.perf_counter(), control=control,
                         log=lambda s: print(s, file=sys.stderr, flush=True))
        prog = res["program_checks"] if control else res["checks"]
        row = {"seed": seed,
               "program": {k: c["value"] for k, c in prog.items()},
               "program_correct": all(c["value"] <= c["limit"]
                                      for c in prog.values())}
        if control:
            row["control"] = {k: c["value"]
                              for k, c in res["checks"].items()}
            row["control_correct"] = res["correct"]
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"workload": args.workload, "rows": rows}
    for k in rows[0]["program"]:
        summary[k] = {"lower": max(r["program"][k] for r in rows),
                      "upper": min(r["control"][k] for r in rows
                                   if "control" in r)}
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
