"""Decode attention: one new query per slot against the slot's cached keys
and values, in every layer of one decode step.  The work counted is what
the algorithm needs: each slot's valid positions only, not the cache's
length."""

SPAN = "decode"         # the benchmark's host span it runs in


def matches(op: str, stats: dict) -> bool:
    """The decode step's one Pallas call, by its custom-call target: the
    profiler names each operation by its HLO text, which for the kernel
    holds ``custom_call_target="tpu_custom_call"`` (``pallas_call`` names
    every kernel ``_kernel``, and XLA names the instruction after the
    custom-VJP wrapper, ``closed_call.N``)."""
    return any('custom_call_target="tpu_custom_call"' in v
               for v in (op, *stats.values()))


def launches(call, config: dict) -> int:
    """Operations of the kernel in one decode step: one per layer."""
    return config["model"]["n_layers"]


def work(call, config: dict) -> tuple[float, float]:
    """(operations, bytes) of one decode step's calls, all layers."""
    m = config["model"]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    _, _, lengths, _ = call
    ctx = float(sum(int(x) for x in lengths))
    flops = 4.0 * hq * hd * ctx                       # q.k and p.v
    nbytes = 2 * 2 * hkv * hd * ctx + 2 * 2 * len(lengths) * hq * hd
    return m["n_layers"] * flops, m["n_layers"] * nbytes
