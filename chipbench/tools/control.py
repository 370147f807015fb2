#!/usr/bin/env python3
"""Readings that set a cell's limit on ``max_logit_gap``, in one process.

    python3 chipbench/tools/control.py --workload minicpm-2b.chat \
        --seconds 15 --seeds 1 2 3 ... --control-seeds 1 2 3

For each seed it serves a window of the cell at its own load and sizes,
exactly as a run does, and reads the number a run compares with the run's
own comparison (``harness.judge``): the widest gap between the float32
reference's best logit and the served token's (the lower reading, over the
seeds).  For each control seed it also reads the same number for the tokens
that the reference computed with int8 and with fp8 weight and activation
products puts first, at each position of the same requests (the control,
which has to read above the limit and come out not correct).  One JSON line
per seed; the benchmark's own runs never run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

QUANTS = ("int8", "fp8")


def readings(cell, seed: int, seconds: float, control: bool,
             refs: dict) -> dict:
    from chipbench import harness

    out = harness.serve(cell, seed, seconds, False, time.perf_counter())
    verdict = harness.judge(out, refs[None])
    row = {"seed": seed, "requests": verdict["requests"],
           "tokens": verdict["tokens"],
           "window_compiles": out.details["window_compiles"],
           "program": verdict["checks"]["max_logit_gap"]["value"],
           "program_correct": verdict["correct"]}
    if control:
        for q in QUANTS:
            v = harness.judge(out, refs[None], refs[q])
            row[q] = v["checks"]["max_logit_gap"]["value"]
            row[f"{q}_correct"] = v["correct"]
    return row


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=())
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.reference import Reference
    cell = spec.cell(args.workload)
    refs = {q: Reference(cell.config, q) for q in (None,) + QUANTS}
    for seed in args.seeds:
        row = readings(cell, seed, args.seconds,
                       seed in args.control_seeds, refs)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
