"""Mixture-of-Experts blocks.

Two production sharding schemes, selected per-arch (config.MoEConfig.impl):

  ep_a2a : experts sharded over the EXPERT-PARALLEL span — the data axis,
           plus the node and pod axes on a cluster mesh
           (ctx.ep_axes, DESIGN.md §15) — with all_to_all
           dispatch/return, + tensor parallelism *inside* each expert
           over the model axis (col/row split of the expert FFN with a
           FlexLink all_reduce).  Used when n_experts %% ep == 0
           (kimi-k2: 384 experts over dp=16 -> 24 experts/rank; one
           chip holds its share, ``MoEConfig.held``, and runs without
           the exchange).
           The all_to_all is FlexLink-backed — MoE dispatch is exactly
           the traffic the paper targets (Fig. 3) — and on a cluster
           mesh it is the RAIL-LOCAL decomposition
           (ctx.ep_all_to_all): intra shuffle + rail-aligned NIC leg
           (+ spine leg), bit-exact vs the flat all_to_all.

  tp     : experts replicated, every expert's FFN hidden dim sharded over
           the model axis; tokens never leave their rank (no a2a), the
           row-parallel combine is a FlexLink all_reduce.  Used when
           n_experts < axis size (mixtral: 8 experts, tp=16).

Both dispatch capacity-based and one-hot-free: tokens are ranked within
their expert via a stable argsort + bincount (no [T, E] one-hot matmuls),
then scattered into [n_experts, capacity, d] buffers.  Dropped tokens
(beyond capacity) fall back to the residual path, Switch-style.

An ep_a2a layer outside any expert-parallel span (one chip) runs without
its exchange: it routes over all ``n_experts``, holds ``MoEConfig.held``
of them (the chip's share of the deployment, all by default) and computes
their part of the result, every held expert over every token of the step,
so no token is dropped at any load (:func:`held_experts`).  What the
experts held elsewhere would add is left out.

Routing (:func:`route`): softmax over the router's logits (Mixtral), or
DeepSeek-V3's sigmoid scores in float32 with a per-expert correction bias
that only chooses the top-k (kimi-k2); the chosen weights are
renormalised and scaled by ``routed_scale``.  Shared experts
(``n_shared``) add one SwiGLU over every token.

Router aux loss (load balance) is returned alongside the output.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ArchConfig, MoEConfig
from repro.models.tp import ParallelCtx
from repro.models import layers as L
from repro.models.layers import silu


# ---------------------------------------------------------------------------
# routing + capacity dispatch (shared by both impls)
# ---------------------------------------------------------------------------

def route(x2d: jax.Array, w_router: jax.Array, moe: MoEConfig,
          bias: Optional[jax.Array] = None):
    """x2d: [T, D] -> (weights [T,k], experts [T,k], aux_loss scalar).
    Softmax weights come in x2d's dtype, sigmoid weights in float32."""
    if moe.scoring == "sigmoid":
        return _route_sigmoid(x2d, w_router, bias, moe)
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, moe.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)   # renormalize top-k
    return w.astype(x2d.dtype), idx, _aux_loss(probs, idx, moe)


def _route_sigmoid(x2d, w_router, bias, moe: MoEConfig):
    """DeepSeek-V3 ``noaux_tc`` with one group: scores ``sigmoid(x W_g)``
    in float32 (the product at full float32 precision); the top-k of
    ``scores + bias`` chosen; their scores renormalised (+1e-20, as the
    model code) and times ``routed_scale``."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(scores + bias.astype(jnp.float32), moe.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * moe.routed_scale
    probs = scores / scores.sum(-1, keepdims=True)
    return w, idx, _aux_loss(probs, idx, moe)


def _aux_loss(probs, idx, moe: MoEConfig):
    """Switch-style aux loss: E * sum_e f_e * p_e."""
    t = probs.shape[0]
    f = jnp.zeros((moe.n_experts,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / (t * moe.top_k))
    p = probs.mean(axis=0)
    return moe.n_experts * jnp.sum(f * p)


def held_weights(weights: jax.Array, experts: jax.Array,
                 moe: MoEConfig) -> jax.Array:
    """[T, n_held] float32: each token's weight on each held expert (0
    where the expert is not among its top-k)."""
    held = moe.first_held + jnp.arange(moe.n_held)
    hit = experts[..., None] == held                     # [T, k, n_held]
    return jnp.sum(jnp.where(hit, weights.astype(jnp.float32)[..., None],
                             0.0), axis=1)


def route_counts(experts: jax.Array, valid: Optional[jax.Array],
                 moe: MoEConfig) -> jax.Array:
    """int32 [n_held + 1]: token-expert pairs of the ``valid`` tokens on
    each held expert, then all their pairs (tokens x top_k)."""
    held = moe.first_held + jnp.arange(moe.n_held)
    hit = experts[..., None] == held                     # [T, k, n_held]
    if valid is None:
        valid = jnp.ones(experts.shape[:1], bool)
    per = jnp.sum(hit & valid[:, None, None], axis=(0, 1), dtype=jnp.int32)
    return jnp.concatenate(
        [per, jnp.sum(valid, dtype=jnp.int32)[None] * moe.top_k])


def capacity_of(t_local: int, moe: MoEConfig) -> int:
    cap = int(math.ceil(t_local * moe.top_k / moe.n_experts
                        * moe.capacity_factor))
    return max(cap, 4)


def dispatch_indices(experts: jax.Array, n_experts: int, capacity: int):
    """experts: [T*k] -> (slot [T*k], keep [T*k]) without one-hot matmuls."""
    tk = experts.shape[0]
    order = jnp.argsort(experts, stable=True)
    sorted_e = experts[order]
    counts = jnp.bincount(experts, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos_in_expert = jnp.arange(tk) - starts[sorted_e]
    keep_sorted = pos_in_expert < capacity
    slot_sorted = sorted_e * capacity + jnp.minimum(pos_in_expert,
                                                    capacity - 1)
    # un-sort back to token order
    inv = jnp.argsort(order, stable=True)
    return slot_sorted[inv], keep_sorted[inv]


def gather_to_buffers(x2d: jax.Array, slots: jax.Array, keep: jax.Array,
                      n_experts: int, capacity: int) -> jax.Array:
    """Scatter tokens into [n_experts * capacity, D] (dropped -> zeros)."""
    d = x2d.shape[-1]
    buf = jnp.zeros((n_experts * capacity, d), x2d.dtype)
    contrib = jnp.where(keep[:, None], x2d, 0)
    return buf.at[jnp.where(keep, slots, n_experts * capacity - 1)].add(
        jnp.where(keep[:, None], contrib, 0))


def combine_from_buffers(buf: jax.Array, slots: jax.Array, keep: jax.Array,
                         weights: jax.Array) -> jax.Array:
    """buf: [E*cap, D]; slots/keep/weights: [T*k] -> [T*k, D]."""
    out = buf[slots]
    return jnp.where(keep[:, None], out, 0) * weights[:, None]


# ---------------------------------------------------------------------------
# expert FFN (TP col/row inside each expert)
# ---------------------------------------------------------------------------

def expert_width(cfg: ArchConfig) -> int:
    return cfg.moe.d_expert or cfg.d_ff


def init_experts(key, cfg: ArchConfig, dtype):
    """GLOBAL shapes [n_held, d, d_expert]; moe_specs shards the expert dim
    over the ep span (ep_a2a) and the hidden dim over model."""
    d, f = cfg.d_model, expert_width(cfg)
    n = cfg.moe.n_held
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    return {
        "w_gate": jax.random.normal(k1, (n, d, f), dtype) * std,
        "w_up": jax.random.normal(k2, (n, d, f), dtype) * std,
        "w_down": jax.random.normal(k3, (n, f, d), dtype) * std,
    }


def expert_ffn(p, x: jax.Array) -> jax.Array:
    """x: [n_local, cap*, D] -> same shape (no collective; caller reduces)."""
    h = silu(jnp.einsum("ecd,edf->ecf", x, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def held_experts(p, x2d: jax.Array, w_held: jax.Array) -> jax.Array:
    """The held experts' part of the layer, dropless: every held expert
    over every token, weighted by ``w_held`` [T, n_held] (zero where a
    token did not choose it).  x2d: [T, D] -> [T, D] float32 (no
    collective; caller reduces)."""
    h = silu(jnp.einsum("td,edf->etf", x2d, p["w_gate"])) * \
        jnp.einsum("td,edf->etf", x2d, p["w_up"])
    out = jnp.einsum("etf,efd->etd", h, p["w_down"])
    return jnp.einsum("etd,te->td", out.astype(jnp.float32), w_held)


# ---------------------------------------------------------------------------
# the two MoE blocks
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ArchConfig, dtype):
    moe = cfg.moe
    kr, ke = jax.random.split(key)
    p = {
        "w_router": jax.random.normal(kr, (cfg.d_model, moe.n_experts),
                                      dtype) * 0.02,
        "experts": init_experts(ke, cfg, dtype),
    }
    if moe.scoring == "sigmoid":
        # the correction bias only chooses experts; float32 as published
        p["router_bias"] = jnp.zeros((moe.n_experts,), jnp.float32)
    if moe.n_shared:
        p["shared"] = L.init_mlp(jax.random.fold_in(key, 2), cfg, dtype,
                                 d_ff=moe.n_shared * expert_width(cfg))
    return p


def moe_specs(cfg: ArchConfig, data_axis, model_axis: str):
    """``data_axis`` is the expert-dim entry: a bare axis name, or the
    outermost-major ep axis tuple on a cluster mesh (ctx.ep_spec_axis())
    — PartitionSpec takes either form unchanged."""
    from jax.sharding import PartitionSpec as P
    e_axis = data_axis if cfg.moe.impl == "ep_a2a" else None
    sp = {
        "w_router": P(None, None),
        "experts": {
            "w_gate": P(e_axis, None, model_axis),
            "w_up": P(e_axis, None, model_axis),
            "w_down": P(e_axis, model_axis, None),
        },
    }
    if cfg.moe.scoring == "sigmoid":
        sp["router_bias"] = P(None)
    if cfg.moe.n_shared:
        sp["shared"] = L.mlp_specs(model_axis)
    return sp


def moe_block(p, x: jax.Array, cfg: ArchConfig,
              ctx: ParallelCtx) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar)."""
    y, aux, _ = moe_layer(p, x, cfg, ctx)
    return y, aux


def moe_layer(p, x: jax.Array, cfg: ArchConfig, ctx: ParallelCtx,
              valid: Optional[jax.Array] = None):
    """x: [B, S, D] -> (out [B, S, D], aux_loss scalar, counts): counts
    is :func:`route_counts` of the tokens ``valid`` [B*S] marks (all by
    default).  Named scopes ``route``, ``experts``, ``shared``."""
    moe = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    with jax.named_scope("route"):
        weights, experts, aux = route(x2d, p["w_router"], moe,
                                      p.get("router_bias"))
        counts = route_counts(experts, valid, moe)
    with jax.named_scope("experts"):
        if moe.impl == "ep_a2a" and ctx.ep_size <= 1:
            y = held_experts(p["experts"], x2d,
                             held_weights(weights, experts, moe))
            y = ctx.tp_all_reduce(y.astype(x.dtype))      # row-parallel
        else:
            assert moe.held is None, "an ep span holds every expert"
            y = _capacity_experts(p, x2d, weights.astype(x.dtype), experts,
                                  moe, ctx)
    if moe.n_shared:
        with jax.named_scope("shared"):
            y = y + L.mlp_block(p["shared"], x2d[None], ctx)[0]
    return y.reshape(b, s, d), aux.astype(jnp.float32), counts


def _capacity_experts(p, x2d, weights, experts, moe: MoEConfig,
                      ctx: ParallelCtx) -> jax.Array:
    """Capacity dispatch (module doc): ep_a2a over its span, or tp."""
    t, d = x2d.shape
    cap = capacity_of(t, moe)
    xk = jnp.repeat(x2d, moe.top_k, axis=0)              # [T*k, D]
    slots, keep = dispatch_indices(experts.reshape(-1), moe.n_experts, cap)
    buf = gather_to_buffers(xk, slots, keep, moe.n_experts, cap)

    if moe.impl == "ep_a2a" and ctx.ep_size > 1:
        ep = ctx.ep_size
        n_local = moe.n_experts // ep
        # [E*cap, D] -> a2a over the ep span: each rank keeps its expert
        # slice of every peer's buffer -> [ep * n_local * cap, D].  On a
        # cluster mesh this is the rail-local decomposition; single-node
        # it is the flat data-axis all_to_all, byte-identically.
        sent = ctx.ep_all_to_all(buf, split_axis=0, concat_axis=0)
        inb = sent.reshape(ep, n_local, cap, d)
        inb = inb.transpose(1, 0, 2, 3).reshape(n_local, ep * cap, d)
        out_loc = expert_ffn(p["experts"], inb)           # TP-sharded d_ff
        out_loc = ctx.tp_all_reduce(out_loc)              # row-parallel
        outb = out_loc.reshape(n_local, ep, cap, d).transpose(1, 0, 2, 3)
        outb = outb.reshape(ep * n_local * cap, d)
        ret = ctx.ep_all_to_all(outb, split_axis=0, concat_axis=0)
        buf_out = ret                                     # [E*cap, D]
    else:
        out_loc = expert_ffn(
            p["experts"], buf.reshape(moe.n_experts, cap, d))
        out_loc = ctx.tp_all_reduce(out_loc)              # row-parallel
        buf_out = out_loc.reshape(moe.n_experts * cap, d)

    yk = combine_from_buffers(buf_out, slots, keep, weights.reshape(-1))
    return yk.reshape(t, moe.top_k, d).sum(axis=1)
