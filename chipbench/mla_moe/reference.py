"""The plain reference for the MLA + MoE tree (Kimi K2, the DeepSeek-V3
layout), written from the published description and importing nothing of
the program.  One sequence at a time, no cache, no absorption, no
batching; float32 with every matrix product at ``Precision.HIGHEST``, or,
for the control, float8 e4m3 operands in every weight product
(``chipbench/reference.py``'s ``matmul``, a scale per row of activations
and per column of weights).  A third mode, ``bf16``, stands for a sound
bfloat16 implementation: bfloat16 operands in every product, float32
sums, the residual stream rounded to bfloat16 after each sublayer.

A layer (DeepSeek-V2 arXiv:2405.04434 §2.1, DeepSeek-V3 arXiv:2412.19437
§2.1):

* attention: ``c_q = RMSNorm(x W_DQ)``, ``q = c_q W_UQ`` split per head
  into nope and rope parts; ``[c_kv | k_r] = x W_DKV``, ``c_kv =
  RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_UKV`` per head; the rope part
  of each query and the one shared ``k_r`` rotated; ``k = [k_nope |
  k_r]``; causal softmax at ``qk_head_dim^-0.5 * mscale(factor,
  mscale_all_dim)^2``; the heads' values through ``W_O``;
* a dense SwiGLU in the first ``first_k_dense_replace`` layers, else the
  expert layer: scores ``sigmoid(x W_g)``; the top-k of ``scores +
  bias`` chosen; their scores over their sum, times
  ``routed_scaling_factor``; ``y = shared(x) + sum over the chosen experts
  this chip holds of w_e FFN_e(x)``.

Rotary positions rotate the two halves of each rope part (``[x1, x2] ->
[x1 cos - x2 sin, x1 sin + x2 cos]``), where the published model rotates
interleaved pairs: the two differ by a permutation of the columns of
``W_UQ``'s and ``W_DKV``'s rope parts, which are random here.  The YaRN
frequencies and the cos/sin factor follow the DeepSeek-V3 model code.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import reference as _dense
from chipbench.reference import rms_norm

F32 = jnp.float32
BF16 = jnp.bfloat16
HIGHEST = lax.Precision.HIGHEST


def matmul(x, w, mode: str):
    """``x @ w`` over the last axis of x and the first of w, in ``mode``
    (``f32``, ``fp8`` or ``bf16``)."""
    if mode == "bf16":
        return jnp.matmul(x.astype(BF16), w.astype(BF16),
                          preferred_element_type=F32)
    return _dense.matmul(x, w, mode)


def rounded(x, mode: str):
    """x as ``mode`` stores activations: rounded to bfloat16 in ``bf16``
    mode, else unchanged (float32)."""
    return x.astype(BF16).astype(F32) if mode == "bf16" else x


def mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_freqs(a: Dict) -> np.ndarray:
    """Inverse frequencies of the rope part (YaRN where configured)."""
    dim, base = a["qk_rope_head_dim"], float(a["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    y = a.get("rope_scaling")
    if not y:
        return extra
    orig = y["original_max_position_embeddings"]

    def corr_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(corr_dim(y["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inter = extra / y["factor"]
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(a: Dict) -> float:
    scale = (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]) ** -0.5
    y = a.get("rope_scaling")
    if y and y.get("mscale_all_dim"):
        scale *= mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def rope(x, pos, a: Dict):
    """x: [S, H, rope]; pos: [S]."""
    ang = pos.astype(F32)[:, None] * jnp.asarray(rope_freqs(a), F32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    y = a.get("rope_scaling")
    if y:
        f = mscale(y["factor"], y.get("mscale", 1.0)) / \
            mscale(y["factor"], y.get("mscale_all_dim", 0.0))
        cos, sin = cos * f, sin * f
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, scale: float, q_block: Optional[int] = None,
              mode: str = "f32"):
    """Causal softmax attention of one sequence: q, k [S, H, dk], v [S,
    H, dv]; queries in blocks of ``q_block`` rows."""
    q, k, v = rounded(q, mode), rounded(k, mode), rounded(v, mode)
    s = q.shape[0]
    kpos = jnp.arange(s)
    outs = []
    step = q_block or s
    for lo in range(0, s, step):
        qb = q[lo:lo + step]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * scale
        qpos = lo + jnp.arange(qb.shape[0])
        scores = jnp.where(qpos[:, None] >= kpos[None, :], scores, -jnp.inf)
        probs = rounded(jax.nn.softmax(scores, axis=-1), mode)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


def mla(x, ap, a: Dict, mode: str, q_block: Optional[int] = None):
    """The attention sublayer on x [S, D] (already normalised)."""
    s = x.shape[0]
    h, r = a["num_attention_heads"], a["kv_lora_rank"]
    nope, rd, vd = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                    a["v_head_dim"])
    eps = a["rms_norm_eps"]
    pos = jnp.arange(s)
    cq = rms_norm(matmul(x, ap["wq_a"], mode), ap["q_norm"], eps)
    q = matmul(cq, ap["wq_b"], mode).reshape(s, h, nope + rd)
    kv = matmul(x, ap["wkv_a"], mode)
    c = rms_norm(kv[:, :r], ap["kv_norm"], eps)
    k_r = rope(kv[:, None, r:], pos, a)                       # [S, 1, rd]
    up = matmul(c, ap["wkv_b"], mode).reshape(s, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, a)], -1)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_r, (s, h, rd))], -1)
    att = attention(q, k, up[..., nope:], softmax_scale(a), q_block, mode)
    return matmul(att.reshape(s, h * vd), ap["wo"], mode)


def swiglu(x, fp, mode: str):
    return matmul(jax.nn.silu(matmul(x, fp["w_gate"], mode)) *
                  matmul(x, fp["w_up"], mode), fp["w_down"], mode)


def route(x, mp, a: Dict, mode: str) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts [S, k], their weights [S, k]) of x [S, D]."""
    scores = jax.nn.sigmoid(matmul(x, mp["w_router"], mode))
    _, idx = lax.top_k(scores + mp["router_bias"].astype(F32),
                       a["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if a.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return idx, w * a["routed_scaling_factor"]


def held_routing(x, mp, a: Dict, mode: str = "f32",
                 held: Optional[Tuple[int, int]] = None):
    """(margin [S], chosen [S, count]) of the held experts on x [S, D]
    (already normalised).  ``chosen``: which held experts are among the
    top-k of ``scores + bias``.  ``margin``: how near the held experts lie
    to the top-k's edge, the least over them of a chosen one's lead over
    the best expert left out, or an unchosen one's shortfall below the
    last expert chosen.  Where it is small, the rounding of a lower
    precision can move a held expert in or out, and with it about a
    ``routed_scaling_factor / k`` share of that expert's output."""
    first, count = held or (a["experts_first"], a["experts_held"])
    k = a["num_experts_per_tok"]
    v = jax.nn.sigmoid(matmul(x, mp["w_router"], mode)) + \
        mp["router_bias"].astype(F32)
    top, _ = lax.top_k(v, k + 1)
    last, left_out = top[:, k - 1:k], top[:, k:k + 1]
    vh = v[:, first:first + count]
    chosen = vh >= last
    margin = jnp.where(chosen, vh - left_out, last - vh)
    return jnp.min(margin, axis=-1), chosen


def moe(x, mp, a: Dict, mode: str, held: Optional[Tuple[int, int]] = None,
        shared: bool = True):
    """The expert layer on x [S, D] (already normalised): the part of the
    experts ``held`` = (first, count) (the file's by default), whose
    weights ``mp["experts"]`` hold, plus the shared expert if
    ``shared``."""
    first, count = held or (a["experts_first"], a["experts_held"])
    idx, w = route(x, mp, a, mode)
    y = jnp.zeros(x.shape, F32)
    for j in range(count):
        we = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)  # [S]
        fp = jax.tree.map(lambda t: t[j], mp["experts"])
        y = y + we[:, None] * swiglu(x, fp, mode)
    if shared and "shared" in mp:
        y = y + swiglu(x, mp["shared"], mode)
    return y


def layer(x, lp, a: Dict, mode: str, dense: bool,
          q_block: Optional[int] = None, routing: bool = False):
    """One decoder layer on x [S, D] (float32).  With ``routing``, an
    expert layer returns ``(x, margin, chosen)``: :func:`held_routing`
    of its input."""
    eps = a["rms_norm_eps"]
    x = rounded(x + mla(rms_norm(x, lp["ln1"], eps), lp["attn"], a, mode,
                        q_block), mode)
    h = rms_norm(x, lp["ln2"], eps)
    if dense:
        return rounded(x + swiglu(h, lp["mlp"], mode), mode)
    out = rounded(x + moe(h, lp["moe"], a, mode), mode)
    return (out,) + held_routing(h, lp["moe"], a, mode) if routing else out


def embed(params, tokens):
    return params["embed"][tokens].astype(F32)


def head(params, x, a: Dict, mode: str):
    x = rms_norm(x, params["final_norm"], a["rms_norm_eps"])
    return matmul(x, params["lm_head"], mode)


def layers(params, a: Dict):
    """[(layer params, dense)] in order."""
    out = []
    for group, dense in (("prefix", True), ("layers", False)):
        if group in params:
            n = jax.tree.leaves(params[group])[0].shape[0]
            out += [(jax.tree.map(lambda t, i=i: t[i], params[group]), dense)
                    for i in range(n)]
    return out


def logits(params, tokens, a: Dict, mode: str = "f32"):
    """Logits [S, V] of one sequence of token ids [S]."""
    x = embed(params, jnp.asarray(tokens))
    for lp, dense in layers(params, a):
        x = layer(x, lp, a, mode, dense)
    return head(params, x, a, mode)
