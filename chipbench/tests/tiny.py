"""Cells at a size the CPU holds, for the benchmark's own tests."""

from __future__ import annotations

import copy

from chipbench import spec

DENSE = {
    "arch": "tiny-dense", "family": "dense", "dtype": "bfloat16",
    "model": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 256, "act": "swiglu",
              "tie_embeddings": True, "norm_eps": 1e-6, "rope_theta": 10000.0},
}
SSM = {
    "arch": "tiny-ssm", "family": "ssm", "dtype": "bfloat16",
    "model": {"n_layers": 2, "d_model": 64, "n_heads": 0, "n_kv_heads": 0,
              "d_ff": 0, "vocab": 256, "tie_embeddings": False,
              "norm_eps": 1e-6,
              "ssm": {"d_state": 16, "head_dim": 16, "expand": 2, "chunk": 8,
                      "conv_width": 4}},
}
CHAT = {
    "engine": {"max_batch": 4, "max_len": 64},
    "arrivals": {"kind": "poisson", "rate": 20.0},
    "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.8, "min": 4,
               "max": 40},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.6, "min": 2,
               "max": 16},
    "trace_seconds": 0.5,
    "check": {"tokens": 40, "limit": None},
}
# arrivals well above what four slots serve, so every slot is in use
FULL = dict(copy.deepcopy(CHAT), arrivals={"kind": "poisson", "rate": 200.0})

E2E = ({"name": "setup_s", "unit": "s"}, {"name": "ttft_p90_ms", "unit": "ms"},
       {"name": "itl_p90_ms", "unit": "ms"})


# max_logit_gap at this size on the CPU, over seeds 1, 2, 3 and 2**33 + 1:
# the program reads at most 0.0007 (dense) and 0.026 (SSM), the fp8 control
# at least 0.052 and 0.124
LIMITS = {"dense": 0.02, "ssm": 0.06}


def cell(config=DENSE, mix=CHAT) -> spec.Cell:
    mix = copy.deepcopy(mix)
    mix["check"]["limit"] = LIMITS[config["family"]]
    return spec.Cell(name="tiny", chips=1, config=copy.deepcopy(config),
                     traffic=mix, end_to_end=E2E, per_layer=())
