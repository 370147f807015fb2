"""The SSD scan's intra-chunk pass (``repro.kernels.ssd_scan``, Pallas): in
a state-space model's prefill, one call per layer over the prompt's chunks
and heads.  Per chunk of ``q`` positions it forms the scores C·Bᵀ (q×q×n),
multiplies each head's masked scores into its dt-scaled inputs (q×q×hd per
head) and forms each head's outgoing state (n×q×hd per head).  The work
counted is the prompt's own positions, cut at the configuration's chunk,
not the padding of its last chunk; the bytes are the pass's inputs and
outputs in float32, each read or written once, as far as they lie in
HBM (``hbm_share``)."""

import re

SPAN = "admit"          # the benchmark's host span it runs in

F32 = 4
# an array in the profiler's HLO text: element type, dimensions, layout
_ARRAY = re.compile(r"\b(f32|bf16|s32)\[([\d,]*)\]\{([^}]*)\}")
_ITEM = {"f32": 4, "bf16": 2, "s32": 4}


def matches(op: str, stats: dict) -> bool:
    """The prefill's Pallas calls, by their custom-call target (the
    profiler names each operation by its HLO text).  In a state-space
    model the intra-chunk pass is the prefill's only Pallas call, and the
    reader's count check refuses a window where that does not hold."""
    return any('custom_call_target="tpu_custom_call"' in v
               for v in (op, *stats.values()))


def hbm_share(op: str) -> float:
    """The share of one call's operand and result bytes that lie in HBM,
    from the operation's HLO text.  XLA stages small arrays in the core's
    own memory (VMEM, memory space 1: ``S(1)`` in the layout) by copies
    that run before the call, so the call's time holds no HBM traffic for
    them; at this cell's prompt lengths it stages all eight.  Where the
    text shows no arrays, every byte is taken to lie in HBM."""
    arrays = _ARRAY.findall(op.split("custom_call_target", 1)[0])
    sizes = [(_ITEM[t] * _count(dims), "S(1)" in layout)
             for t, dims, layout in arrays]
    total = sum(b for b, _ in sizes)
    if total == 0:
        return 1.0
    return sum(b for b, staged in sizes if not staged) / total


def _count(dims: str) -> int:
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def launches(call, config: dict) -> int:
    """One call per layer for every prompt the admission pass admitted."""
    _, _, plens = call
    return config["model"]["n_layers"] * len(plens)


def _sizes(config: dict) -> tuple[int, int, int, int]:
    m = config["model"]
    s = m["ssm"]
    return (s["expand"] * m["d_model"] // s["head_dim"], s["head_dim"],
            s["d_state"], s["chunk"])


def prompt_work(t: int, config: dict) -> tuple[float, float]:
    """(operations, bytes) of one layer's call on a prompt of ``t``
    tokens."""
    nh, hd, n, chunk = _sizes(config)
    flops, chunks = 0.0, 0
    for start in range(0, t, chunk):
        q = min(chunk, t - start)
        flops += 2.0 * q * q * n + nh * (2.0 * q * q * hd + 2.0 * q * n * hd)
        chunks += 1
    # read: dt-scaled inputs, the cumulative decay as three operands, B, C;
    # written: the chunk outputs and each chunk's outgoing state per head
    nbytes = F32 * (2 * t * nh * hd + 3 * t * nh + 2 * t * n
                    + chunks * nh * n * hd)
    return flops, float(nbytes)


def work(call, config: dict) -> tuple[float, float]:
    """(operations, bytes) of one admission pass's calls, all layers."""
    _, _, plens = call
    layers = config["model"]["n_layers"]
    flops = nbytes = 0.0
    for t in plens:
        f, b = prompt_work(int(t), config)
        flops, nbytes = flops + layers * f, nbytes + layers * b
    return flops, nbytes
