#!/usr/bin/env python3
"""Runs one cell of the chip benchmark and prints its result.

    python3 chipbench/run.py --workload minicpm-2b.chat --seed 7 \
        --seconds 45 --trace 0

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` a profiler trace is taken for a few seconds inside the
window and the metrics are the cell's per-layer metrics.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``; ``checks`` last, each
number compared with its limit).  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    result = harness.run(spec.cell(args.workload), args.seed, args.seconds,
                         bool(args.trace), T_PROCESS)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
