"""The ssd_scan kernel's share of its roofline in the prefill, in percent.

As ``readers.roofline``, with two differences:

* the kernel's operations are taken from the whole trace, not only from
  inside the admission spans.  On the v5e the device's events can shift
  against the host's spans partway through a trace (seen: ~57 ms after a
  140-ms admission), which leaves the prefill's calls after the shift
  outside their spans.  Counting the whole trace is exact here: a
  state-space model's only Pallas call is the prefill's, and every
  admission waits for its first token, so the trace holds the calls of the
  admissions inside it and no others;
* a call's bytes count only as far as they lie in HBM
  (``kernels/ssd_scan.hbm_share``): operands that XLA stages in the
  core's own memory beforehand cost the call no HBM time.

Prompts are prefilled one after another, so the calls in time order are
the admitted prompts' in admission order, one per layer each."""

import sys

from chipbench import spec


def read(window):
    k = spec.kernel("ssd_scan")
    stats = window.trace.get("op_stats", {})
    ops = sorted((e for e in window.trace["devices"][0]
                  if k.matches(e[0], stats.get(e[0], {}))),
                 key=lambda e: e[1])
    calls = window.calls(k.SPAN)
    want = sum(k.launches(call, window.config) for call in calls)
    if not ops or len(ops) != want:
        print(f"ssd_scan_roofline not read: {len(ops)} operations in the "
              f"trace, {want} launches expected", file=sys.stderr)
        return None
    layers = window.config["model"]["n_layers"]
    plens = [t for _, _, ts in calls for t in ts]
    least = 0.0
    for i, t in enumerate(plens):
        flops, nbytes = k.prompt_work(int(t), window.config)
        for name, _, _ in ops[i * layers:(i + 1) * layers]:
            least += max(flops / window.peaks["bf16_flops"],
                         k.hbm_share(name) * nbytes
                         / window.peaks["hbm_bytes_per_s"])
    return least / sum(d for _, _, d in ops) * 100.0
