"""A state-space model's decode step against its memory roofline: the
least bytes a step moves, at the chip's HBM peak, over the device time of
the operations inside the benchmark's decode spans, in percent.

The least bytes of one step are every weight read once, and each active
slot's recurrent state (``h`` and the convolution's context) read and
written once, at the dtypes the configuration states.  Device time is the
union of the operations that start inside a decode span, so that an
operation nested in another (a loop and its body) counts once."""

import numpy as np

from chipbench import tracing, weights


def weight_bytes(config: dict) -> int:
    """Bytes of every weight, as the benchmark makes them (norms in
    float32, the rest in the configuration's dtype)."""
    item = np.dtype(config["dtype"]).itemsize
    return sum(int(np.prod(shape)) * (4 if kind == "norm" else item)
               for shape, kind in weights.shapes(config).values())


def slot_state_bytes(config: dict) -> int:
    """Bytes of one slot's recurrent state, all layers."""
    m = config["model"]
    s = m["ssm"]
    di, n = s["expand"] * m["d_model"], s["d_state"]
    dtypes = config["state_dtype"]
    h = (di // s["head_dim"]) * s["head_dim"] * n
    conv = (s["conv_width"] - 1) * (di + 2 * n)
    return m["n_layers"] * (h * np.dtype(dtypes["h"]).itemsize
                            + conv * np.dtype(dtypes["conv"]).itemsize)


def least_bytes(config: dict, active: int) -> int:
    """Bytes one decode step with ``active`` slots in use has to move."""
    return weight_bytes(config) + 2 * active * slot_state_bytes(config)


def read(window):
    spans = [h for h in window.trace["host"] if h[0] == "decode"]
    if not spans or not window.decodes:
        return None
    ops = tracing.within(window.trace["devices"][0], spans)
    device_s = sum(e - s for s, e in tracing.union(ops))
    if device_s <= 0:
        return None
    least = sum(least_bytes(window.config, int(sum(active)))
                for *_, active in window.decodes)
    return least / window.peaks["hbm_bytes_per_s"] / device_s * 100.0
