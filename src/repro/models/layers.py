"""Shared model layers: RMSNorm, RoPE, chunked (flash-style) attention with
GQA/SWA, SwiGLU MLP — all tensor-parallel through ParallelCtx.

Conventions:
  * activations are [B, S, D]; attention heads live in [B, S, H, hd];
  * TP shards Q heads (and KV heads when divisible) over the model axis:
    column-parallel QKV/up projections, row-parallel out/down projections
    with a FlexLink all_reduce;
  * attention is computed in chunks over the KV axis with running
    max/denominator (flash-style) so 32k prefill never materializes S^2;
  * GQA with n_kv < tp replicates KV heads across shards (Megatron's KV
    duplication), keeping every shard self-contained.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models.config import ArchConfig, YarnConfig
from repro.models.tp import ParallelCtx

ATTN_CHUNK = 512  # KV-axis chunk for the streaming softmax


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(dt) * w


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(scale) + 1``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(head_dim: int, theta: float, yarn: YarnConfig) -> np.ndarray:
    """YaRN's blended inverse frequencies (DeepSeek-V3 model code):
    ``theta^(-2i/d)`` below the correction dim of ``beta_fast``,
    ``theta^(-2i/d) / factor`` above that of ``beta_slow``, a linear ramp
    between (its top bumped by 0.001 where the two meet)."""
    def corr_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (np.arange(0, head_dim, 2) / head_dim)
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return (extra / yarn.factor * ramp + extra * (1 - ramp)).astype(
        np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Optional[YarnConfig] = None) -> jax.Array:
    """x: [B, S, H, hd]; positions: [S] or [B, S].  The two halves of
    each head rotate together.  With ``yarn`` the frequencies are
    :func:`yarn_freqs` and cos and sin carry ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``."""
    hd = x.shape[-1]
    if yarn is None:
        freqs = rope_freqs(hd, theta)                    # [hd/2]
    else:
        freqs = jnp.asarray(yarn_freqs(hd, theta, yarn))
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        ang = ang[None, :, None, :]                      # [1, S, 1, hd/2]
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[:, :, None, :]                         # [B, S, 1, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention
# ---------------------------------------------------------------------------

def _mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
          window: Optional[int], kv_valid) -> jax.Array:
    """Boolean keep-mask [..., Sq, Skv]; q_pos may be [Sq] or [B, Sq] and
    kv_valid a scalar or [B] (per-slot serving positions)."""
    qp = q_pos[..., :, None]                      # [(B,) Sq, 1]
    kp = k_pos[None, :]                           # [1, Skv]
    m = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        m &= qp >= kp
    if window is not None:
        m &= (qp - kp) < window
    if kv_valid is not None:
        kv = jnp.asarray(kv_valid)
        if kv.ndim:                               # per-batch [B]
            m = m & (kp < kv[:, None, None])
        else:
            m = m & (kp < kv)
    return m


def _softmax_chunk(carry, qg, q_pos, ci, kci, vci, *, chunk: int, k_offset,
                   causal: bool, window: Optional[int], kv_valid,
                   local_len: Optional[int]):
    """One chunk's streaming-softmax update, the body that
    :func:`chunked_attention` scans and :func:`paged_attention` loops.

    carry: (running max, denominator, accumulator) of [B,Hkv,g,Sq(,dv)];
    qg: scaled f32 queries [B,Sq,Hkv,g,dk]; ci: chunk index; kci, vci:
    chunk ``ci`` of the keys [B,chunk,Hkv,dk] and values [B,chunk,Hkv,dv]
    (value width dv may differ from key width dk), keys at local
    positions ci*chunk + j (global k_offset + local).  A chunk every row
    masks wholly leaves a finite running max as it is (alpha = 1) and adds
    exact zeros."""
    m_run, l_run, acc = carry
    k_local = ci * chunk + jnp.arange(chunk)
    k_pos = k_offset + k_local
    kf = kci.astype(jnp.float32)
    vf = vci.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bchd->bhgqc", qg, kf)      # [B,Hkv,g,Sq,chunk]
    keep = _mask(q_pos, k_pos, causal, window, kv_valid)
    if local_len is not None:
        keep = keep & (k_local < local_len)
    if keep.ndim == 2:                           # [Sq, chunk]
        keep = keep[None, None, None]
    else:                                        # [B, Sq, chunk]
        keep = keep[:, None, None]
    s = jnp.where(keep, s, -jnp.inf)
    m_new = jnp.maximum(m_run, s.max(axis=-1))       # [B,Hkv,g,Sq]
    # guard all-masked rows (m == -inf): exp(-inf - -inf) -> use where
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - m_safe[..., None], -jnp.inf))
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_run),
                      jnp.exp(m_run - m_safe), 0.0)  # rescale old
    l_new = l_run * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhgqc,bchd->bhgqd", p, vf)
    return m_new, l_new, acc_new


def _softmax_init(b: int, hkv: int, group: int, sq: int, hd: int):
    """The empty (running max, denominator, accumulator); ``hd`` is the
    value width."""
    m0 = jnp.full((b, hkv, group, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, sq, hd), jnp.float32)
    return m0, l0, a0


def _softmax_out(acc: jax.Array, l_run: jax.Array, dtype) -> jax.Array:
    """Normalize the accumulator [B,Hkv,g,Sq,dv] into [B,Sq,Hq,dv]."""
    b, hkv, group, sq, hd = acc.shape
    denom = jnp.maximum(l_run, 1e-30)
    out = acc / denom[..., None]                          # [B,Hkv,g,Sq,hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hkv * group, hd)
    return out.astype(dtype)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, window: Optional[int] = None,
                      q_offset=0, k_offset=0,
                      kv_valid: Optional[jax.Array] = None,
                      chunk: int = ATTN_CHUNK,
                      with_stats: bool = False,
                      scale: Optional[float] = None):
    """Streaming-softmax attention.

    q: [B, Sq, Hq, hd]; k: [B, Skv, Hkv, hd], v: [B, Skv, Hkv, dv] with
    Hq % Hkv == 0.  ``scale`` multiplies the scores (default hd^-0.5).
    Positions are q_offset+i / k_offset+j (offsets may be traced scalars —
    used by the sequence-sharded decode path).  When ``with_stats`` the
    returned value is (out, running_max, denom) for cross-shard LSE merges.
    """
    b, sq, hq, hd = q.shape
    skv, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qf = q.astype(jnp.float32) * scale
    q_off = jnp.asarray(q_offset)
    q_pos = (q_off[..., None] + jnp.arange(sq)) if q_off.ndim \
        else (q_off + jnp.arange(sq))

    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    local_len = None
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # padded slots must be masked by LOCAL index: with a nonzero
        # k_offset (sequence-sharded caches) the pad slots alias global
        # positions that a kv_valid bound alone would wrongly admit.
        local_len = skv
    kc = k.reshape(b, n_chunks, chunk, hkv, hd)
    vc = v.reshape(b, n_chunks, chunk, hkv, dv)

    qg = qf.reshape(b, sq, hkv, group, hd)               # [B,Sq,Hkv,g,hd]

    def step(carry, xs):
        ci, kci, vci = xs                                # kci: [B,chunk,Hkv,hd]
        return _softmax_chunk(carry, qg, q_pos, ci, kci, vci, chunk=chunk,
                              k_offset=k_offset, causal=causal,
                              window=window, kv_valid=kv_valid,
                              local_len=local_len), None

    init = _softmax_init(b, hkv, group, sq, dv)
    idx = jnp.arange(n_chunks)
    (m_f, l_f, acc_f), _ = lax.scan(
        step, init,
        (idx, jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))

    if with_stats:
        # caller merges across shards (lse_merge) before normalizing
        return acc_f, m_f, l_f
    return _softmax_out(acc_f, l_f, q.dtype)


def lse_merge(parts):
    """Merge per-shard (acc, m, l) attention partials (same shapes).

    parts: list of tuples — returns normalized [B,Hkv,g,Sq,hd] accumulator.
    """
    m_glob = parts[0][1]
    for _, m, _ in parts[1:]:
        m_glob = jnp.maximum(m_glob, m)
    m_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
    l_tot = jnp.zeros_like(parts[0][2])
    acc_tot = jnp.zeros_like(parts[0][0])
    for acc, m, l in parts:
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_tot = l_tot + l * alpha
        acc_tot = acc_tot + acc * alpha[..., None]
    return acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]


# ---------------------------------------------------------------------------
# attention block (TP)
#
# Unified GQA sharding that works for every assigned config (kv heads from 2
# to 16 against tp=16) and every mode (train / prefill / decode /
# sequence-sharded decode):
#   * Q and O projections are head-sharded over the model axis (column/row
#     parallel, FlexLink all_reduce on the row combine);
#   * K/V projections are stored FULL (replicated) — KV heads are small — and
#     each shard *slices* the KV heads its local Q heads attend to before the
#     matmul, so no KV-head padding/replication tricks are needed;
#   * decode caches are sharded over the model axis on the SEQUENCE dim
#     (each shard holds its KV-head slice x its sequence slice); partial
#     attention is merged across shards with a log-sum-exp psum.
# ---------------------------------------------------------------------------

def head_layout(cfg: ArchConfig, ctx: ParallelCtx):
    """(hq_local, kv_width, group_local): local Q heads, KV heads a shard
    needs, and Q-heads-per-KV-head locally."""
    tp = max(ctx.tp_size, 1)
    hq = cfg.n_heads
    hkv = cfg.n_kv_heads
    assert hq % tp == 0 or tp == 1, (hq, tp)
    hq_l = hq // tp if tp > 1 else hq
    group = hq // hkv
    if hq_l >= group:
        assert hq_l % group == 0, (hq_l, group)
        kv_w = hq_l // group
    else:
        assert group % hq_l == 0, (hq_l, group)
        kv_w = 1
    return hq_l, kv_w, hq_l // kv_w


def init_attention(key, cfg: ArchConfig, dtype):
    """GLOBAL param shapes (shard_map in_specs produce the local views)."""
    if cfg.mla is not None:
        return init_mla(key, cfg, dtype)
    d, hd = cfg.d_model, cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(key, 4)
    std = 0.02
    p = {
        "wq": jax.random.normal(k1, (d, cfg.n_heads * hd), dtype) * std,
        "wk": jax.random.normal(k2, (d, cfg.n_kv_heads * hd), dtype) * std,
        "wv": jax.random.normal(k3, (d, cfg.n_kv_heads * hd), dtype) * std,
        "wo": jax.random.normal(k4, (cfg.n_heads * hd, d), dtype) * std,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def attention_specs(cfg: ArchConfig, model_axis: str):
    """PartitionSpecs matching init_attention (Q/O sharded, K/V replicated)."""
    from jax.sharding import PartitionSpec as P
    if cfg.mla is not None:
        return mla_specs(model_axis)
    p = {
        "wq": P(None, model_axis),
        "wk": P(None, None),
        "wv": P(None, None),
        "wo": P(model_axis, None),
    }
    if cfg.qkv_bias:
        p["bq"] = P(model_axis)
        p["bk"] = P(None)
        p["bv"] = P(None)
    return p


def _kv_slice(p, cfg: ArchConfig, ctx: ParallelCtx, which: str):
    """Slice the KV-projection columns for this shard's KV heads."""
    hd = cfg.head_dim_
    hq_l, kv_w, _ = head_layout(cfg, ctx)
    if ctx.tp_size <= 1 or kv_w == cfg.n_kv_heads:
        w = p["w" + which]
        bias = p.get("b" + which)
        return w, bias
    idx = ctx.tp_index()
    first_kv = (idx * hq_l * cfg.n_kv_heads) // cfg.n_heads
    w = lax.dynamic_slice_in_dim(p["w" + which], first_kv * hd, kv_w * hd,
                                 axis=1)
    bias = None
    if ("b" + which) in p:
        bias = lax.dynamic_slice_in_dim(p["b" + which], first_kv * hd,
                                        kv_w * hd, axis=0)
    return w, bias


def attention_block(p, x: jax.Array, cfg: ArchConfig, ctx: ParallelCtx, *,
                    causal: bool = True, positions=None,
                    kv_cache=None, cache_pos=None, seq_shard=None,
                    window_override="cfg",
                    xattn_kv=None) -> Tuple[jax.Array, Optional[tuple]]:
    """One attention sublayer (pre-norm handled by the caller).

    kv_cache: (k, v) of [B, S_cache_local, kv_w, hd] — decode mode; x holds
      the new token(s), cache_pos the global write position.
    seq_shard: cache sequence dim is sharded over the model axis (long
      contexts); partial attention is LSE-merged with a psum.
    xattn_kv: precomputed (k, v) [B, S_enc, kv_w, hd] for cross-attention.
    window_override: "cfg" uses cfg.sliding_window; None/int overrides (the
      --swa-override decode variant for full-attention archs).
    Returns (out [B,S,D], new_cache).
    """
    if cfg.mla is not None:
        if kv_cache is not None or xattn_kv is not None:
            raise NotImplementedError(
                "latent attention (MLA) decodes through PagedServeEngine "
                "and its latent paged pool, not a dense K/V cache")
        return mla_block(p, x, cfg, ctx, positions=positions), None
    b, s, d = x.shape
    hd = cfg.head_dim_
    hq_l, kv_w, group_l = head_layout(cfg, ctx)
    window = cfg.sliding_window if window_override == "cfg" \
        else window_override
    if positions is None:
        positions = jnp.arange(s)

    q = jnp.einsum("bsd,df->bsf", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = q.reshape(b, s, hq_l, hd)

    new_cache = None
    if xattn_kv is not None:
        k, v = xattn_kv
        out = chunked_attention(q, k, v, causal=False, window=None)
    else:
        if seq_shard is not None:
            # sequence-sharded decode: every shard attends ALL heads over
            # its sequence slice, so K/V use the full head set.
            wk, bk = p["wk"], p.get("bk")
            wv, bv = p["wv"], p.get("bv")
        else:
            wk, bk = _kv_slice(p, cfg, ctx, "k")
            wv, bv = _kv_slice(p, cfg, ctx, "v")
        k = jnp.einsum("bsd,df->bsf", x, wk)
        v = jnp.einsum("bsd,df->bsf", x, wv)
        if bk is not None:
            k, v = k + bk, v + bv
        kw = cfg.n_kv_heads if seq_shard is not None else kv_w
        k = k.reshape(b, s, kw, hd)
        v = v.reshape(b, s, kw, hd)
        if cfg.rope_theta:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

        if kv_cache is None:
            out = chunked_attention(q, k, v, causal=causal, window=window)
        elif seq_shard is None:
            ck, cv = kv_cache
            pos_arr = jnp.asarray(cache_pos)
            if pos_arr.ndim:                     # per-slot positions [B]
                assert s == 1, "vector cache_pos requires single-token steps"
                sl = jnp.arange(ck.shape[1])
                hit = (sl[None] == pos_arr[:, None])[:, :, None, None]
                ck = jnp.where(hit, k.astype(ck.dtype), ck)
                cv = jnp.where(hit, v.astype(cv.dtype), cv)
            else:
                ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype),
                                                     cache_pos, axis=1)
                cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype),
                                                     cache_pos, axis=1)
            new_cache = (ck, cv)
            # causal=True keeps multi-token decode steps (s>1, the
            # memory-amortization lever in EXPERIMENTS §Perf) correct; for
            # s==1 it is equivalent to the kv_valid bound alone.
            out = chunked_attention(q, ck, cv, causal=True, window=window,
                                    q_offset=cache_pos,
                                    kv_valid=pos_arr + s)
        else:
            out, new_cache = _seq_sharded_decode(
                q, k, v, kv_cache, cache_pos, cfg, ctx, window,
                seq_shard=seq_shard)

    o = jnp.einsum("bsf,fd->bsd", out.reshape(b, s, hq_l * hd), p["wo"])
    o = ctx.tp_all_reduce(o)       # row-parallel combine — FlexLink path
    return o, new_cache


def _seq_sharded_decode(q, k_new, v_new, kv_cache, cache_pos, cfg, ctx,
                        window, seq_shard="model"):
    """Decode attention over a cache whose SEQUENCE dim is sharded over the
    model axis (and the data axis too for batch=1 long-context).

    Q heads are sharded over the model axis but the sequence is as well, so
    a shard's local Q rows would only ever see its own slice.  Standard
    flash-decode distribution: (1) all_gather the (tiny) Q across the model
    axis so every shard holds ALL heads, (2) write the new token's full-head
    K/V into the owning shard's slice, (3) local partial attention over the
    slice, (4) distributed log-sum-exp merge (pmax/psum), (5) each shard
    slices back its OWN Q heads for the row-parallel out-projection.
    """
    b, s, hq_l, hd = q.shape
    ck, cv = kv_cache
    s_local = ck.shape[1]
    tp = max(ctx.tp_size, 1)
    shard_idx = ctx.tp_index()
    if seq_shard == "model_data":
        # batch=1 long-context: sequence sharded over data x model
        seq_idx = ctx.dp_index() * tp + ctx.tp_index()
    else:
        seq_idx = shard_idx
    offset = seq_idx * s_local

    # (1) full-head Q on every shard (bytes: B x Hq x hd — negligible).
    # Issued as its own in-flight plan (DESIGN.md §11): the gather
    # overlaps the K/V cache write below, which needs no Q — the engine's
    # StepProgram await_all closes the window.
    if tp > 1:
        with ctx.issue("q_ag"):
            qg = ctx.tp_all_gather(q.transpose(2, 0, 1, 3), tiled=True)
        q_full = qg.transpose(1, 2, 0, 3)           # [B, s, Hq, hd]
    else:
        q_full = q
    hq = q_full.shape[2]

    # (2) conditional write of the new token's K/V into the owning shard
    local_pos = cache_pos - offset
    owns = (local_pos >= 0) & (local_pos < s_local)
    safe_pos = jnp.clip(local_pos, 0, s_local - s)
    ck_new = lax.dynamic_update_slice_in_dim(ck, k_new.astype(ck.dtype),
                                             safe_pos, axis=1)
    cv_new = lax.dynamic_update_slice_in_dim(cv, v_new.astype(cv.dtype),
                                             safe_pos, axis=1)
    ck = jnp.where(owns, ck_new, ck)
    cv = jnp.where(owns, cv_new, cv)

    # (3) local partial attention with global position offsets
    acc, m, l = chunked_attention(
        q_full, ck, cv, causal=True, window=window, q_offset=cache_pos,
        k_offset=offset, kv_valid=cache_pos + s, with_stats=True)
    # (4) distributed LSE merge over the sequence-sharding axes
    m_glob = ctx.tp_pmax_small(m)
    if seq_shard == "model_data":
        m_glob = ctx.dp_pmax_small(m_glob)
    m_safe = jnp.where(jnp.isfinite(m_glob), m_glob, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_glob = ctx.tp_psum_small(l * alpha)
    acc_glob = ctx.tp_psum_small(acc * alpha[..., None])
    if seq_shard == "model_data":
        l_glob = ctx.dp_psum_small(l_glob)
        acc_glob = ctx.dp_psum_small(acc_glob)
    out = acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
    # out: [B, Hkv, group, s, hd] over ALL heads -> [B, s, Hq, hd]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, hq, hd)
    # (5) slice back this shard's own Q heads for the row-parallel out proj
    if tp > 1:
        out = lax.dynamic_slice_in_dim(out, shard_idx * hq_l, hq_l, axis=2)
    return out.astype(q.dtype), (ck, cv)


# ---------------------------------------------------------------------------
# paged attention (continuous-batching serving, DESIGN.md §13)
# ---------------------------------------------------------------------------

def paged_attention(q: jax.Array, k_pool: jax.Array,
                    v_pool: Optional[jax.Array],
                    block_tables: jax.Array, *, positions: jax.Array,
                    kv_valid: jax.Array,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    v_width: Optional[int] = None) -> jax.Array:
    """Streaming-softmax attention of packed single-token rows over a
    paged pool, bit-identical to gathering each row's whole view (index i
    of it is position i) and calling :func:`chunked_attention` on it.

    q: [T, 1, Hq, hd]; k_pool: [n_blocks, block, Hkv, hd]; v_pool:
    [n_blocks, block, Hkv, dv], or None for a latent pool whose values
    are the first ``v_width`` columns of its keys (latent attention's
    absorbed form: one "head" of keys ``[c_kv | k_rope]``, values
    ``c_kv``); block_tables: [T, max_blocks]; positions / kv_valid: [T].
    ``scale`` multiplies the scores (default hd^-0.5).

    Iteration c gathers chunk c of every row straight from the pool and
    applies chunked_attention's update to it, so no whole view is ever
    built.  Where ``block`` divides ``chunk`` the gather takes whole
    blocks (``chunk // block`` per row); otherwise it takes position rows.
    Table slots past ``max_blocks`` read block 0 and are masked by local
    index, as chunked_attention masks its padding.  The loop stops after
    the last chunk that holds a position below some row's ``kv_valid``:
    the chunks beyond are masked for every row and would change no bit.
    An all-padding step runs no iteration and returns exact zeros.

    Named scopes: ``kv_gather`` on the in-loop gather, ``attend`` on the
    math."""
    t, sq, hq, hd = q.shape
    nb, bs, hkv, _ = k_pool.shape
    dv = v_width if v_pool is None else v_pool.shape[-1]
    group = hq // hkv
    chunk = ATTN_CHUNK                  # chunked_attention's, bit for bit
    maxb = block_tables.shape[1]
    span = maxb * bs
    n_chunks = -(-span // chunk)
    local_len = span if n_chunks * chunk != span else None
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    with jax.named_scope("attend"):
        qf = q.astype(jnp.float32) * scale
        q_pos = positions[:, None] + jnp.arange(sq)
        qg = qf.reshape(t, sq, hkv, group, hd)           # [T,Sq,Hkv,g,hd]
        init = _softmax_init(t, hkv, group, sq, dv)
    with jax.named_scope("kv_gather"):
        if chunk % bs == 0:
            per = chunk // bs                   # whole blocks per chunk
            tables = jnp.pad(block_tables,
                             ((0, 0), (0, n_chunks * per - maxb)))

            def gather(pool, c):
                ids = lax.dynamic_slice_in_dim(tables, c * per, per, axis=1)
                return pool[ids].reshape(t, chunk, hkv, pool.shape[-1])
        else:
            n_tab = -(-n_chunks * chunk // bs)
            tables = jnp.pad(block_tables, ((0, 0), (0, n_tab - maxb)))
            k_pool = k_pool.reshape(nb * bs, hkv, hd)
            if v_pool is not None:
                v_pool = v_pool.reshape(nb * bs, hkv, dv)

            def gather(pool, c):
                k_local = c * chunk + jnp.arange(chunk)
                ids = jnp.take(tables, k_local // bs, axis=1)  # [T, chunk]
                return pool[ids * bs + k_local % bs]
    # the chunks up to the last that holds a live position of some row
    n_live = jnp.minimum((jnp.max(kv_valid) + chunk - 1) // chunk, n_chunks)

    def body(c, carry):
        with jax.named_scope("kv_gather"):
            kci = gather(k_pool, c)                      # [T,chunk,Hkv,hd]
            vci = kci[..., :dv] if v_pool is None else gather(v_pool, c)
        with jax.named_scope("attend"):
            return _softmax_chunk(carry, qg, q_pos, c, kci, vci, chunk=chunk,
                                  k_offset=0, causal=True, window=window,
                                  kv_valid=kv_valid, local_len=local_len)

    _, l_f, acc_f = lax.fori_loop(0, n_live, body, init)
    with jax.named_scope("attend"):
        return _softmax_out(acc_f, l_f, q.dtype)


def _paged_write(pools, new, block_tables: jax.Array, layer,
                 positions: jax.Array, kv_valid: jax.Array):
    """Scatter the packed rows ``new`` (one [T, ...] array per pool) into
    the stacked pools ``[L, n_blocks, block, ...]``, in layer ``layer``'s
    blocks, at each row's position.  Padding rows (``kv_valid`` 0) write
    nowhere: an out-of-bounds row, dropped.  Returns (new pools, the row
    tables offset to the layer's blocks, as paged_attention reads them
    from the pools reshaped to ``[L * n_blocks, block, ...]``)."""
    n_layers, nb, bs = pools[0].shape[:3]
    flat = [a.reshape(n_layers * nb * bs, *a.shape[3:]) for a in pools]
    tables = block_tables + layer * nb             # this layer's blocks
    with jax.named_scope("attn"), jax.named_scope("kv_write"):
        blk = positions // bs
        off = positions % bs
        phys = jnp.take_along_axis(tables, blk[:, None], axis=1)[:, 0]
        dest = jnp.where(kv_valid > 0, phys * bs + off, n_layers * nb * bs)
        return tuple(f.at[dest].set(n.astype(a.dtype),
                                    mode="drop").reshape(a.shape)
                     for a, f, n in zip(pools, flat, new)), tables


def paged_attention_block(p, x: jax.Array, cfg: ArchConfig,
                          ctx: ParallelCtx, *, positions: jax.Array,
                          kv_valid: jax.Array, pools, layer, block_tables,
                          window_override="cfg",
                          impl: str = "reference"):
    """One attention sublayer over a PAGED KV pool (packed serving layout).

    x            : [T, 1, D] — T packed single-token rows (prefill-chunk
                   rows and decode rows alike; the engine packs them)
    positions    : [T] int32 per-row positions (0 for padding rows)
    kv_valid     : [T] int32 — row t attends cache positions < kv_valid[t];
                   0 marks a bucket-padding row (zero attention mass, no
                   cache write)
    pools        : (k_pool, v_pool) [L, n_blocks, block_size, kv_w, hd] —
                   EVERY layer's physical block pool, stacked
    layer        : this sublayer's index into the stack; it writes and
                   reads its own blocks (block ``j`` of layer ``l`` is row
                   ``l * n_blocks + j`` of the flattened stack) in place
    block_tables : [T, max_blocks] int32 — per-ROW tables (the engine
                   gathers its per-request tables out to packed rows)
    impl         : "reference" (:func:`paged_attention`: chunked_attention's
                   streaming softmax with each chunk gathered from the
                   pool, bit-identical to the wave engine's dense-cache
                   path) or "kernel" (kernels/flash_decode.py)

    The new K/V are scattered into the pool BEFORE attention, so later
    rows of the same request in the same step see earlier rows' K/V —
    intra-step causality is then exactly the kv_valid bound.  Padding rows
    scatter to a dropped out-of-bounds index (zero pool writes) and read
    an all-masked accumulator (exact-zero output).

    The ops carry named scopes for the device trace (metadata only):
    ``qkv_proj``; ``attn`` with ``kv_write``, ``kv_gather`` and
    ``attend``; ``o_proj``.

    Returns (out [T, 1, D], (new_k_pool, new_v_pool)), the stacked pools
    with this layer's new rows written.
    """
    b, s, d = x.shape
    assert s == 1, "paged attention packs single-token rows"
    hd = cfg.head_dim_
    hq_l, kv_w, _ = head_layout(cfg, ctx)
    window = cfg.sliding_window if window_override == "cfg" \
        else window_override

    with jax.named_scope("qkv_proj"):
        q = jnp.einsum("bsd,df->bsf", x, p["wq"])
        if "bq" in p:
            q = q + p["bq"]
        q = q.reshape(b, s, hq_l, hd)
        wk, bk = _kv_slice(p, cfg, ctx, "k")
        wv, bv = _kv_slice(p, cfg, ctx, "v")
        k = jnp.einsum("bsd,df->bsf", x, wk)
        v = jnp.einsum("bsd,df->bsf", x, wv)
        if bk is not None:
            k, v = k + bk, v + bv
        k = k.reshape(b, s, kv_w, hd)
        v = v.reshape(b, s, kv_w, hd)
        if cfg.rope_theta:
            pos2 = positions[:, None]             # [T, 1] per-row
            q = apply_rope(q, pos2, cfg.rope_theta)
            k = apply_rope(k, pos2, cfg.rope_theta)

    new_pools, tables = _paged_write(pools, (k[:, 0], v[:, 0]),
                                     block_tables, layer, positions, kv_valid)
    n_layers, nb = pools[0].shape[:2]
    blocks = [a.reshape(n_layers * nb, *a.shape[2:]) for a in new_pools]
    with jax.named_scope("attn"):
        if impl == "kernel":
            from repro.kernels import ops as K
            with jax.named_scope("attend"):
                out = K.paged_flash_decode(q[:, 0], *blocks, tables,
                                           kv_valid, window=window)[:, None]
        else:
            out = paged_attention(q, *blocks, tables, positions=positions,
                                  kv_valid=kv_valid, window=window)

    with jax.named_scope("o_proj"):
        o = jnp.einsum("bsf,fd->bsd", out.reshape(b, s, hq_l * hd), p["wo"])
        o = ctx.tp_all_reduce(o)   # row-parallel combine — FlexLink path
    return o, new_pools


# ---------------------------------------------------------------------------
# latent attention (MLA; DeepSeek-V2 arXiv:2405.04434 §2.1)
#
# Heads shard over the model axis as GQA heads do: the up-projections
# W_UQ and W_UKV and the output W_O split by head, the low-rank
# down-projections (W_DQ, W_DKV) and their norms whole on every shard.
# Each head's columns of W_UQ are [nope | rope], of W_UKV [k_nope | v].
# Rotary positions rotate the two halves of the rope part (a permutation
# of the published interleaved pairs, i.e. of random weight columns).
# ---------------------------------------------------------------------------

def mla_softmax_scale(cfg: ArchConfig) -> float:
    """``qk_head_dim^-0.5``, times ``mscale(factor, mscale_all_dim)^2``
    under YaRN."""
    scale = cfg.mla.qk_head_dim ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ArchConfig, dtype):
    """GLOBAL shapes of one MLA sublayer (mla_specs shards them)."""
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    std = 0.02
    return {
        "wq_a": jax.random.normal(ks[0], (d, m.q_lora_rank), dtype) * std,
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": jax.random.normal(ks[1], (m.q_lora_rank,
                                          h * m.qk_head_dim), dtype) * std,
        "wkv_a": jax.random.normal(ks[2], (d, m.latent_width), dtype) * std,
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": jax.random.normal(
            ks[3], (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)),
            dtype) * std,
        "wo": jax.random.normal(ks[4], (h * m.v_head_dim, d), dtype) * std,
    }


def mla_specs(model_axis: str):
    from jax.sharding import PartitionSpec as P
    return {"wq_a": P(None, None), "q_norm": P(None),
            "wq_b": P(None, model_axis), "wkv_a": P(None, None),
            "kv_norm": P(None), "wkv_b": P(None, model_axis),
            "wo": P(model_axis, None)}


def _mla_q(p, x, cfg: ArchConfig, positions):
    """(q_nope, roped q_rope) [B,S,H_local,·]: ``c_q = RMSNorm(x W_DQ)``,
    ``q = c_q W_UQ`` split per head."""
    m = cfg.mla
    b, s, _ = x.shape
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"],
                  cfg.norm_eps)
    q = jnp.einsum("bsr,rf->bsf", cq, p["wq_b"]).reshape(
        b, s, -1, m.qk_head_dim)
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        cfg.rope_theta, cfg.rope_scaling)
    return q[..., :m.qk_nope_head_dim], q_rope


def _mla_latent(p, x, cfg: ArchConfig, positions):
    """(normalised c_kv [B,S,r], roped shared key k_r [B,S,rope]):
    ``[c_kv | k_r] = x W_DKV``."""
    r = cfg.mla.kv_lora_rank
    kv = jnp.einsum("bsd,dw->bsw", x, p["wkv_a"])
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_r = apply_rope(kv[..., None, r:], positions, cfg.rope_theta,
                     cfg.rope_scaling)[:, :, 0]
    return c, k_r


def _mla_up(p, cfg: ArchConfig, heads: int):
    """W_UKV per head: (W_UK [r, H, nope], W_UV [r, H, v])."""
    m = cfg.mla
    w = p["wkv_b"].reshape(m.kv_lora_rank, heads,
                           m.qk_nope_head_dim + m.v_head_dim)
    return w[..., :m.qk_nope_head_dim], w[..., m.qk_nope_head_dim:]


def mla_block(p, x: jax.Array, cfg: ArchConfig, ctx: ParallelCtx, *,
              positions=None) -> jax.Array:
    """Causal MLA over [B, S, D] (train / prefill), keys and values
    decompressed per head: ``k = [c_kv W_UK | k_r]``, ``v = c_kv W_UV``.
    Named scopes ``mla_q``, ``mla_kv``, ``attn``, ``mla_out``."""
    m = cfg.mla
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    with jax.named_scope("mla_q"):
        q_nope, q_rope = _mla_q(p, x, cfg, positions)
        heads = q_nope.shape[2]
        q = jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], -1)
    with jax.named_scope("mla_kv"):
        c, k_r = _mla_latent(p, x, cfg, positions)
        w_uk, w_uv = _mla_up(p, cfg, heads)
        k_nope = jnp.einsum("bsr,rhn->bshn", c, w_uk)
        v = jnp.einsum("bsr,rhv->bshv", c, w_uv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_r[:, :, None].astype(k_nope.dtype),
            (b, s, heads, m.qk_rope_head_dim))], -1)
    with jax.named_scope("attn"):
        out = chunked_attention(q, k, v, causal=True,
                                scale=mla_softmax_scale(cfg))
    with jax.named_scope("mla_out"):
        o = jnp.einsum("bsf,fd->bsd", out.reshape(b, s, -1), p["wo"])
        return ctx.tp_all_reduce(o)


def mla_paged_block(p, x: jax.Array, cfg: ArchConfig, ctx: ParallelCtx, *,
                    positions: jax.Array, kv_valid: jax.Array,
                    pool: jax.Array, layer, block_tables: jax.Array):
    """One MLA sublayer of packed single-token rows over the latent paged
    pool of every layer ``[L, n_blocks, block, pool_width]``, of which it
    writes and reads layer ``layer``'s blocks in place, in the absorbed
    form: the pool holds each position's normalised ``c_kv`` and roped
    ``k_r`` (then zeros to ``pool_width``); a row's query is ``[q_nope
    W_UK^T | q_rope | 0]`` per head against it, one shared key "head"
    whose values are ``c_kv``; the r-wide context goes through ``W_UV``,
    then ``W_O``.  The same scores as :func:`mla_block`'s:
    ``q_nope . (c W_UK) = (q_nope W_UK^T) . c``.

    The write and the gather are :func:`paged_attention_block`'s: the
    new latents are scattered before attention (padding rows write
    nowhere), and :func:`paged_attention` gathers each 512-position chunk
    from the pool up to the tick's deepest row.  Named scopes ``mla_q``,
    ``mla_kv``, ``attn`` (``kv_write``, ``kv_gather``, ``attend``),
    ``mla_out``.  Returns (out [T, 1, D], new pool)."""
    m = cfg.mla
    b, s, _ = x.shape
    assert s == 1, "paged attention packs single-token rows"
    pos2 = positions[:, None]
    n_layers, nb, bs, width = pool.shape
    pad = ((0, 0),) * 3 + ((0, width - m.latent_width),)
    with jax.named_scope("mla_q"):
        q_nope, q_rope = _mla_q(p, x, cfg, pos2)
        w_uk, w_uv = _mla_up(p, cfg, q_nope.shape[2])
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk,
                           preferred_element_type=jnp.float32)
        q = jnp.pad(jnp.concatenate([q_lat, q_rope.astype(jnp.float32)],
                                    -1), pad)
    with jax.named_scope("mla_kv"):
        c, k_r = _mla_latent(p, x, cfg, pos2)
        latent = jnp.pad(jnp.concatenate([c, k_r.astype(c.dtype)], -1),
                         pad[1:])[:, 0]
    (new_pool,), tables = _paged_write((pool,), (latent,), block_tables,
                                       layer, positions, kv_valid)
    with jax.named_scope("attn"):
        out = paged_attention(
            q, new_pool.reshape(n_layers * nb, bs, 1, width), None, tables,
            positions=positions, kv_valid=kv_valid,
            scale=mla_softmax_scale(cfg), v_width=m.kv_lora_rank)
    with jax.named_scope("mla_out"):
        ctx_v = jnp.einsum("bshr,rhv->bshv", out.astype(x.dtype), w_uv)
        o = jnp.einsum("bsf,fd->bsd", ctx_v.reshape(b, s, -1), p["wo"])
        return ctx.tp_all_reduce(o), new_pool


# ---------------------------------------------------------------------------
# MLP (SwiGLU, TP col/row parallel)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ArchConfig, dtype, d_ff=None):
    """GLOBAL shapes; sharded col/row by mlp_specs."""
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    std = 0.02
    return {
        "w_gate": jax.random.normal(k1, (d, f), dtype) * std,
        "w_up": jax.random.normal(k2, (d, f), dtype) * std,
        "w_down": jax.random.normal(k3, (f, d), dtype) * std,
    }


def mlp_specs(model_axis: str):
    from jax.sharding import PartitionSpec as P
    return {"w_gate": P(None, model_axis), "w_up": P(None, model_axis),
            "w_down": P(model_axis, None)}


def mlp_block(p, x: jax.Array, ctx: ParallelCtx) -> jax.Array:
    h = silu(jnp.einsum("bsd,df->bsf", x, p["w_gate"])) * \
        jnp.einsum("bsd,df->bsf", x, p["w_up"])
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return ctx.tp_all_reduce(out)  # row-parallel combine — FlexLink path
