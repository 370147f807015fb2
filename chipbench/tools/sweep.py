#!/usr/bin/env python3
"""A sweep of offered rates for an open-loop cell, to find its knee: the
highest rate at which the queue does not grow across the window.

    python3 chipbench/tools/sweep.py --workload minicpm-2b.chat \
        --seconds 30 --rates 1.5 2 2.5 3 3.5

Each rate is one window of the cell with only the rate changed.  One JSON
line per rate: requests due and finished, the queue at the window's close,
median time to first token of the requests due in each quarter of the
window (a growing queue shows as a rising median), the tails, and the
tokens emitted per second.
"""

import argparse
import copy
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    base = spec.cell(args.workload)
    for rate in args.rates:
        mix = copy.deepcopy(base.traffic)
        mix["arrivals"]["rate"] = rate
        cell = dataclasses.replace(base, traffic=mix)
        out = harness.serve(cell, args.seed, args.seconds, False,
                            time.perf_counter())
        sched, srv = out.srv.sched, out.srv
        nw = sched.n_window
        ttft = [((t[0] - sched.due[i]) * 1e3 if t else float("inf"), i)
                for i, t in enumerate(srv.times[:nw])]
        quarters = [statistics.median(v for v, i in ttft
                                      if q * nw // 4 <= i < (q + 1) * nw // 4)
                    for q in range(4)]
        itl = [(b - a) * 1e3 for t in srv.times[:nw] if t
               for a, b in zip(t, t[1:])]
        emitted = sum(x <= args.seconds for t in srv.times if t for x in t)
        print(json.dumps({
            "rate": rate, "due": nw, "finished": out.details["finished"],
            "queue_at_close": out.details["backlog_at_close"],
            "ttft_p50_ms_by_quarter": quarters,
            "ttft_p90_ms": harness.percentile([v for v, _ in ttft], 90),
            "itl_p50_ms": statistics.median(itl),
            "itl_p90_ms": harness.percentile(itl, 90),
            "tokens_per_s": emitted / args.seconds,
            "setup_s": out.setup_s}), flush=True)
        del out


if __name__ == "__main__":
    main()
