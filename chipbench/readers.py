"""The arithmetic behind the per-layer metrics.  Each metric has its own
reader in ``metrics/<name>.py``, which names the function here that it
reads with; a reader returns None where the traced window holds nothing for
it to read."""

from __future__ import annotations

import sys

from . import spec


def admit_ms(w) -> float | None:
    """Host milliseconds in ``ServingEngine._admit`` per admitted request
    (prefill, the slot write and the first token's argmax), over the whole
    measured window."""
    n = sum(len(plens) for _, _, plens in w.run_admits)
    if n == 0:
        return None
    return sum(t1 - t0 for t0, t1, plens in w.run_admits if plens) / n * 1e3


def decode_step_ms(w) -> float | None:
    """Host milliseconds per decode step, from its call until its logits are
    ready, over the whole measured window."""
    if not w.run_decodes:
        return None
    return (sum(t1 - t0 for t0, t1, *_ in w.run_decodes)
            / len(w.run_decodes) * 1e3)


def idle_share(w) -> float:
    """Percent of the traced window in which no operation ran on the
    device."""
    return (1.0 - w.busy_s / (w.t1 - w.t0)) * 100.0


def _layer_flops(m: dict, ctx: float) -> float:
    """Forward operations of one decoder layer for one token that attends
    to ``ctx`` positions (weight products count 2 per multiply-add)."""
    d = m["d_model"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    proj = 2 * d * (2 * hq * hd + 2 * hkv * hd)
    return proj + 2 * 3 * d * m["d_ff"] + 4 * hq * hd * ctx


def model_flops(w) -> float:
    """Model operations of every prompt token admitted and every token
    decoded in the window: the layers, and the output head where logits are
    taken (the last prompt position, and each decoded token)."""
    m = w.config["model"]
    head = 2 * m["d_model"] * m["vocab"]
    total = 0.0
    for _, _, plens in w.admits:
        for t in plens:
            # position i attends to i + 1 positions
            per_layer = (t * _layer_flops(m, 0)
                         + (_layer_flops(m, 1) - _layer_flops(m, 0))
                         * t * (t + 1) / 2)
            total += m["n_layers"] * per_layer + head
    for _, _, lengths, active in w.decodes:
        for ln, a in zip(lengths, active):
            if a:   # a float, as int32 lengths overflow the layers' sum
                total += m["n_layers"] * _layer_flops(m, float(ln)) + head
    return total


def mfu(w) -> float | None:
    """Model operations over the window's length times the chip's bf16
    peak, in percent."""
    if not w.admits and not w.decodes:
        return None
    return model_flops(w) / ((w.t1 - w.t0) * w.peaks["bf16_flops"]) * 100.0


def roofline(w, kernel: str) -> float | None:
    """The least time the kernel's work needs on this chip (operations over
    peak FLOP/s or bytes over peak bandwidth, whichever is longer), over the
    kernel's device time, in percent.  Where the trace holds another number
    of the kernel's operations than its count says the calls in the window
    make, the operations found are not the kernel's alone, and the share is
    not read."""
    k = spec.kernel(kernel)
    ops = w.kernel_ops(k)
    calls = w.calls(k.SPAN)
    want = sum(k.launches(call, w.config) for call in calls)
    if not ops or len(ops) != want:
        print(f"{kernel}_roofline not read: {len(ops)} operations in the "
              f"trace, {want} launches expected", file=sys.stderr)
        return None
    least = 0.0
    for call in calls:
        flops, nbytes = k.work(call, w.config)
        least += max(flops / w.peaks["bf16_flops"],
                     nbytes / w.peaks["hbm_bytes_per_s"])
    return least / sum(d for _, _, d in ops) * 100.0
