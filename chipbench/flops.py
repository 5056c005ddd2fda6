"""Operations the benchmark counts, from a configuration's
shapes (the ``arch`` block of a configuration file).  Model operations
only: a multiply-add is two, recomputation (remat) and padding rows are
not counted, and causal attention counts the keys a query may see."""

from __future__ import annotations

from typing import Dict, Iterable, List


def _hd(a: Dict) -> int:
    return a.get("head_dim") or a["d_model"] // a["n_heads"]


def layer_matmul_params(a: Dict) -> int:
    """Weights a token multiplies in one dense layer: Q, K, V, O and the
    SwiGLU gate, up and down projections."""
    d, hd = a["d_model"], _hd(a)
    q = a["n_heads"] * hd
    kv = a["n_kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * a["d_ff"]


def attention_flops(a: Dict, keys: float) -> float:
    """Forward operations of one query over ``keys`` keys, all layers:
    the scores and the weighted sum of values, for every query head."""
    return 4.0 * a["n_layers"] * a["n_heads"] * _hd(a) * keys


def serve_flops(a: Dict, positions: Iterable[int], sampled: int) -> float:
    """Forward operations of serving: one packed row per position in
    ``positions`` (the row at position p attends p + 1 keys), plus the
    output head for the ``sampled`` rows whose logits are read."""
    pos: List[int] = list(positions)
    body = 2.0 * a["n_layers"] * layer_matmul_params(a) * len(pos)
    attn = attention_flops(a, sum(pos) + len(pos))
    return body + attn + 2.0 * a["d_model"] * a["vocab"] * sampled
