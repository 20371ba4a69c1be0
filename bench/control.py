#!/usr/bin/env python3
"""Readings that set the limits of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

Runs the cell on the chip once per seed, in one process, at the cell's own
size and load (``--seconds`` of window each), and prints for each seed one
JSON line: every compared number as the program reads it (the lower
readings) and as the control reads it (the upper ones), each judged by
the same comparison that decides ``correct``.  The control is the plain
reference with its reads one beat stale put in the program's place: it
breaks the configuration's guarantee that a beat's queries see the
writes admitted in that beat, and each EB's reading of its own writes.
The benchmark's own runs never run it.  Exits non-zero unless every
program run comes out correct and every control run does not.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = run.Cell(run.ROOT, args.workload)
    sys.path.insert(0, run.SRC)
    devices = run.init_jax(require_tpu=True, chips=cell.chips)
    t_start, cstats = T_START, run.CompileStats()
    separated = True
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, devices,
                           t_start, cstats, control=True)
        info, ctrl = res["info"], res["control"]
        separated &= res["correct"] and not ctrl["correct"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": {"correct": res["correct"],
                        **{k: c["value"] for k, c in res["checks"].items()}},
            "control": {"correct": ctrl["correct"],
                        **{k: c["value"] for k, c in ctrl["checks"].items()},
                        "wrong_by_template": ctrl["wrong_by_template"]},
            "answers_checked": info["answers_checked"],
            "attempted": res["attempted"], "paths": info["paths"],
            "setup_s": round(res["view"].setup_s, 3),
            "held_over_writes": info["held_over_writes"]}), flush=True)
        t_start = time.perf_counter()
    return 0 if separated else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except run.BenchError as e:
        print(f"control: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
