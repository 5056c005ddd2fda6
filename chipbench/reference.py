"""The plain reference: a LLaMA-style dense decoder (RMSNorm, GQA
attention with rotary positions, SwiGLU MLP, untied output head) in
``jax.numpy``, written from the published description and importing
nothing of the program.  It runs in float32 with every matrix product at
``Precision.HIGHEST``, or, for the control, in float8, the step below the
configuration's bfloat16: both operands of every weight product rounded
to e4m3 (a scale per row of activations and per column of weights).

Rotary positions rotate the two halves of each head (``[x1, x2] ->
[x1 cos - x2 sin, x1 sin + x2 cos]``, frequencies ``theta^(-2i/hd)``).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _hd(a: Dict) -> int:
    return a.get("head_dim") or a["d_model"] // a["n_heads"]


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``;
    returns the rounded values (exact in bfloat16) and the scales."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return q, scale


def matmul(x, w, mode: str):
    """``x @ w`` over the last axis of x and the first of w."""
    x = x.astype(F32)
    w = w.astype(F32)
    if mode == "f32":
        return jnp.matmul(x, w, precision=HIGHEST)
    if mode == "fp8":
        return _fp8_matmul(x, w)
    raise ValueError(mode)


def _fp8_matmul(x, w):
    """e4m3 operands (a scale per row of x and per column of w), the
    product accumulated in float32."""
    qx, sx = _fp8(x, -1)
    qw, sw = _fp8(w, 0)
    return jnp.matmul(qx, qw, preferred_element_type=F32) * sx * sw


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(F32)


def rope(x, pos, theta):
    """x: [..., S, H, hd]; pos: [S]."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * freqs[None, :]          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, q_block=None):
    """Causal softmax attention; q [B, S, Hq, hd], k and v [B, S, Hkv, hd]
    with each key/value head serving Hq / Hkv consecutive query heads.
    ``q_block`` computes the queries in blocks of that many rows."""
    b, s, hq, hd = q.shape
    rep = hq // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    kpos = jnp.arange(s)
    outs = []
    step = q_block or s
    for lo in range(0, s, step):
        qb = q[:, lo:lo + step]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST)
        scores = scores / math.sqrt(hd)
        qpos = lo + jnp.arange(qb.shape[1])
        scores = jnp.where(qpos[:, None] >= kpos[None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                               precision=HIGHEST))
    return jnp.concatenate(outs, axis=1)


def layer(x, lp, a: Dict, mode: str, q_block=None):
    """One decoder layer on x [B, S, D] (float32)."""
    b, s, _ = x.shape
    hd, hq, hkv = _hd(a), a["n_heads"], a["n_kv_heads"]
    eps = a["norm_eps"]
    pos = jnp.arange(s)
    h = rms_norm(x, lp["ln1"], eps)
    q = matmul(h, lp["attn"]["wq"], mode).reshape(b, s, hq, hd)
    k = matmul(h, lp["attn"]["wk"], mode).reshape(b, s, hkv, hd)
    v = matmul(h, lp["attn"]["wv"], mode).reshape(b, s, hkv, hd)
    if a.get("rope_theta"):
        q, k = rope(q, pos, a["rope_theta"]), rope(k, pos, a["rope_theta"])
    att = attention(q, k, v, q_block)
    x = x + matmul(att.reshape(b, s, hq * hd), lp["attn"]["wo"], mode)
    h = rms_norm(x, lp["ln2"], eps)
    gate = matmul(h, lp["mlp"]["w_gate"], mode)
    up = matmul(h, lp["mlp"]["w_up"], mode)
    return x + matmul(jax.nn.silu(gate) * up, lp["mlp"]["w_down"], mode)


def embed(params, tokens):
    return params["embed"][tokens].astype(F32)


def head(params, x, a: Dict, mode: str):
    """Final norm and output head: logits [..., V] in float32."""
    x = rms_norm(x, params["final_norm"], a["norm_eps"])
    return matmul(x, params["lm_head"], mode)


def logits(params, tokens, a: Dict, mode: str):
    x = embed(params, tokens)
    for i in range(a["n_layers"]):
        x = layer(x, jax.tree.map(lambda t: t[i], params["layers"]), a, mode)
    return head(params, x, a, mode)
