"""A tiny serving cell for the CPU tests: the real cell's traffic and
metrics at sizes a test run can hold, built without the harness's look
for a chip."""

from __future__ import annotations

import copy
from pathlib import Path

from chipbench import bench

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent

TINY_ARCH = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "head_dim": 16, "d_ff": 128, "vocab": 512,
             "rope_theta": 10000.0, "norm_eps": 1e-5, "qkv_bias": False,
             "tie_embeddings": False, "param_dtype": "bfloat16"}

#: serving compares logits, whose spread grows with the width and the
#: depth: the serve tests keep deepseek-67b's published d_model and the
#: cell's four layers, so that a served token's gap reads on the scale the
#: cell's limit was set on
TINY_SERVE_ARCH = dict(TINY_ARCH, n_layers=4, d_model=8192, n_heads=8,
                       head_dim=32, d_ff=512)


def cell(name: str, arch=TINY_ARCH, **traffic_overrides) -> bench.Cell:
    """The cell ``name`` with a tiny configuration and its traffic file
    changed as given (nested dicts are merged one level down)."""
    real = bench.load_cell(name, ROOT)
    config = copy.deepcopy(real.config)
    config["arch"] = dict(arch)
    traffic = copy.deepcopy(real.traffic)
    for k, v in traffic_overrides.items():
        if isinstance(v, dict) and isinstance(traffic.get(k), dict):
            traffic[k] = {**traffic[k], **v}
        else:
            traffic[k] = v
    return bench.Cell(name, real.chips, config, traffic, real.end_to_end,
                      real.per_layer)


def tiny_serve(**over) -> bench.Cell:
    base = {"engine": {"max_requests": 4, "cache_len": 96,
                       "max_tokens_in_flight": 32},
            "arrivals": {"rate": 20.0,
                         "prompt": {"median": 16, "sigma": 0.9, "lo": 4,
                                    "hi": 48},
                         "output": {"median": 8, "sigma": 0.7, "lo": 2,
                                    "hi": 24}},
            "drain_seconds": 120, "check": {"requests": 3}}
    base.update(over)
    return cell("serve.deepseek-67b.chat", TINY_SERVE_ARCH, **base)
