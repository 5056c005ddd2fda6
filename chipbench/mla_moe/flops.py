"""Operations the benchmark counts for the MLA + MoE tree, from a
configuration file's published keys.  Model operations only: a
multiply-add is two, padding rows are not counted, and causal attention
counts the keys a query may see.

Attention is counted at the published head widths, decompressed (scores
over ``qk_nope_head_dim + qk_rope_head_dim``, values over ``v_head_dim``,
for every head), not at the absorbed form's widths the decode path runs,
so that a later prefill path is judged on the same count.  The routed
experts count the token-expert pairs that fell on the experts this chip
holds (the program's counter); the shared expert and the head count from
shapes.
"""

from __future__ import annotations

from typing import Dict, Iterable, List


def mla_params(a: Dict) -> int:
    """Weights a token multiplies in one MLA sublayer."""
    d, h = a["hidden_size"], a["num_attention_heads"]
    qr, r = a["q_lora_rank"], a["kv_lora_rank"]
    nope, rope, vd = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
    return (d * qr + qr * h * (nope + rope) + d * (r + rope)
            + r * h * (nope + vd) + h * vd * d)


def layer_params(a: Dict, dense: bool) -> int:
    """Weights every token multiplies in one layer: attention, then the
    dense SwiGLU, or the router and the shared expert (the routed experts
    are counted by pairs, ``expert_params``)."""
    d = a["hidden_size"]
    if dense:
        return mla_params(a) + 3 * d * a["intermediate_size"]
    return (mla_params(a) + d * a["n_routed_experts"]
            + 3 * d * a["n_shared_experts"] * a["moe_intermediate_size"])


def expert_params(a: Dict) -> int:
    return 3 * a["hidden_size"] * a["moe_intermediate_size"]


def attention_flops(a: Dict, keys: float) -> float:
    """Forward operations of one query over ``keys`` keys, all layers:
    scores over the query-key width and the weighted sum of values, for
    every head."""
    width = a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"]
    return 2.0 * a["num_hidden_layers"] * a["num_attention_heads"] * \
        width * keys


def serve_flops(a: Dict, positions: Iterable[int], sampled: int,
                held_pairs: int) -> float:
    """Forward operations of serving: one packed row per position in
    ``positions`` (the row at position p attends p + 1 keys), the routed
    experts for ``held_pairs`` token-expert pairs, and the output head
    for the ``sampled`` rows whose logits are read."""
    pos: List[int] = list(positions)
    npre = a["first_k_dense_replace"]
    nmoe = a["num_hidden_layers"] - npre
    body = 2.0 * len(pos) * (npre * layer_params(a, True)
                             + nmoe * layer_params(a, False))
    experts = 2.0 * expert_params(a) * held_pairs
    attn = attention_flops(a, sum(pos) + len(pos))
    return body + experts + attn + 2.0 * a["hidden_size"] * \
        a["vocab_size"] * sampled
