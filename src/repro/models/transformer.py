"""The LM engine: one generic decoder that instantiates all 10 assigned
architectures from ArchConfig (dense / MoE / SSM / hybrid / enc-dec / VLM).

Engineering choices (DESIGN.md §5):
  * params are stored with GLOBAL shapes; `param_specs` builds the matching
    PartitionSpec tree; `shard_map` produces the local views the layer code
    operates on;
  * layers are STACKED on a leading [L] dim and applied with ``lax.scan`` —
    HLO size and compile time are O(1) in depth (deepseek's 95 layers
    compile like 1);
  * the vocabulary is model-axis-parallel end to end: embedding lookup is a
    masked-local-lookup + FlexLink all_reduce, the LM head produces local
    vocab shards, and cross-entropy uses the distributed log-sum-exp
    (Megatron's vocab-parallel loss) — logits are never materialized
    globally;
  * decode caches are sequence-sharded over the model axis (DESIGN §5);
  * activation checkpointing (remat) wraps each scanned block body.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig
from repro.models.tp import ParallelCtx
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# init + specs
# ---------------------------------------------------------------------------

def _stack_init(key, n: int, init_fn):
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def _stack_specs(specs):
    return jax.tree.map(lambda s: P(*((None,) + tuple(s))), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _dense_block_init(cfg: ArchConfig, dtype):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": L.init_attention(k1, cfg, dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "mlp": L.init_mlp(k2, cfg, dtype),
        }
    return init


def _dense_block_specs(cfg: ArchConfig, model_axis: str):
    return {
        "ln1": P(None),
        "attn": L.attention_specs(cfg, model_axis),
        "ln2": P(None),
        "mlp": L.mlp_specs(model_axis),
    }


def _moe_block_init(cfg: ArchConfig, dtype):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "attn": L.init_attention(k1, cfg, dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "moe": M.init_moe(k2, cfg, dtype),
        }
    return init


def _moe_block_specs(cfg: ArchConfig, data_axis: str, model_axis: str):
    return {
        "ln1": P(None),
        "attn": L.attention_specs(cfg, model_axis),
        "ln2": P(None),
        "moe": M.moe_specs(cfg, data_axis, model_axis),
    }


def _ssm_block_init(cfg: ArchConfig, dtype):
    def init(key):
        return {
            "ln": jnp.ones((cfg.d_model,), dtype),
            "ssm": S.init_ssm(key, cfg, dtype),
        }
    return init


def _ssm_block_specs(model_axis: str):
    return {"ln": P(None), "ssm": S.ssm_specs(model_axis)}


def init_params(key, cfg: ArchConfig, ctx: Optional[ParallelCtx] = None):
    """GLOBAL-shaped parameter tree for any family."""
    cfg.validate()
    dtype = cfg.dtype
    keys = jax.random.split(key, 8)
    p: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_padded, cfg.d_model),
                                   dtype) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            keys[1], (cfg.d_model, cfg.vocab_padded), dtype) * 0.02

    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = _stack_init(keys[2], cfg.n_layers,
                                  _dense_block_init(cfg, dtype))
    elif fam == "moe":
        npre = cfg.moe.n_dense_prefix
        if npre:
            p["prefix"] = _stack_init(keys[3], npre,
                                      _dense_block_init(cfg, dtype))
        p["layers"] = _stack_init(keys[2], cfg.n_layers - npre,
                                  _moe_block_init(cfg, dtype))
    elif fam == "ssm":
        p["layers"] = _stack_init(keys[2], cfg.n_layers,
                                  _ssm_block_init(cfg, dtype))
    elif fam == "hybrid":
        p["layers"] = _stack_init(keys[2], cfg.n_layers,
                                  _ssm_block_init(cfg, dtype))
        p["shared_attn"] = _dense_block_init(cfg, dtype)(keys[4])
    elif fam == "encdec":
        p["enc_layers"] = _stack_init(keys[2], cfg.encdec.n_enc_layers,
                                      _dense_block_init(cfg, dtype))
        p["enc_norm"] = jnp.ones((cfg.d_model,), dtype)

        def dec_init(key):
            k1, k2, k3 = jax.random.split(key, 3)
            blk = _dense_block_init(cfg, dtype)(k1)
            blk["ln_x"] = jnp.ones((cfg.d_model,), dtype)
            blk["xattn"] = L.init_attention(k2, cfg, dtype)
            return blk
        p["layers"] = _stack_init(keys[3], cfg.n_layers, dec_init)
    else:
        raise ValueError(fam)
    return p


def param_specs(cfg: ArchConfig, data_axis: str = "data",
                model_axis: str = "model"):
    """PartitionSpec tree matching init_params."""
    sp: Dict[str, Any] = {
        "embed": P(model_axis, None),           # vocab-parallel
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        sp["lm_head"] = P(None, model_axis)
    fam = cfg.family
    dense_sp = _dense_block_specs(cfg, model_axis)
    if fam in ("dense", "vlm"):
        sp["layers"] = _stack_specs(dense_sp)
    elif fam == "moe":
        if cfg.moe.n_dense_prefix:
            sp["prefix"] = _stack_specs(dense_sp)
        sp["layers"] = _stack_specs(
            _moe_block_specs(cfg, data_axis, model_axis))
    elif fam == "ssm":
        sp["layers"] = _stack_specs(_ssm_block_specs(model_axis))
    elif fam == "hybrid":
        sp["layers"] = _stack_specs(_ssm_block_specs(model_axis))
        sp["shared_attn"] = dense_sp
    elif fam == "encdec":
        sp["enc_layers"] = _stack_specs(dense_sp)
        sp["enc_norm"] = P(None)
        dec_sp = dict(dense_sp)
        dec_sp["ln_x"] = P(None)
        dec_sp["xattn"] = L.attention_specs(cfg, model_axis)
        sp["layers"] = _stack_specs(dec_sp)
    return sp


# ---------------------------------------------------------------------------
# embedding + loss (vocab-parallel)
# ---------------------------------------------------------------------------

def embed_tokens(p, tokens: jax.Array, cfg: ArchConfig,
                 ctx: ParallelCtx) -> jax.Array:
    """Vocab-parallel embedding: masked local lookup + FlexLink all_reduce."""
    table = p["embed"]                           # local [V_l, D]
    v_l = table.shape[0]
    if ctx.tp_size > 1:
        start = ctx.tp_index() * v_l
        local_id = tokens - start
        valid = (local_id >= 0) & (local_id < v_l)
        emb = jnp.where(valid[..., None],
                        table[jnp.clip(local_id, 0, v_l - 1)], 0)
        emb = ctx.tp_all_reduce(emb)
    else:
        emb = table[tokens]
    return emb


def lm_logits_local(p, x: jax.Array, cfg: ArchConfig,
                    ctx: ParallelCtx) -> jax.Array:
    """[B,S,D] -> local vocab-shard logits [B,S,V_l] (never gathered).

    Columns beyond the true vocab (padding for divisibility) are masked to
    -inf so they vanish from softmax/argmax."""
    w = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    if cfg.vocab_padded != cfg.vocab:
        v_l = logits.shape[-1]
        gid = ctx.tp_index() * v_l + jnp.arange(v_l)
        logits = jnp.where(gid < cfg.vocab, logits, -jnp.inf)
    return logits


def vocab_parallel_xent(logits_l: jax.Array, labels: jax.Array,
                        ctx: ParallelCtx, vocab: int) -> jax.Array:
    """Cross-entropy over model-axis-sharded logits (distributed LSE)."""
    v_l = logits_l.shape[-1]
    lf = logits_l.astype(jnp.float32)
    # stop_gradient: the max is a numerical-stability shift whose gradient
    # cancels, and pmax has no differentiation rule anyway.
    m = ctx.tp_pmax_small(lax.stop_gradient(lf.max(axis=-1)))  # [B,S]
    z = ctx.tp_psum_small(jnp.exp(lf - m[..., None]).sum(-1))  # [B,S]
    start = ctx.tp_index() * v_l
    local_id = labels - start
    valid = (local_id >= 0) & (local_id < v_l)
    picked = jnp.take_along_axis(
        lf, jnp.clip(local_id, 0, v_l - 1)[..., None], axis=-1)[..., 0]
    label_logit = ctx.tp_psum_small(jnp.where(valid, picked, 0.0))
    nll = jnp.log(z) + m - label_logit
    return nll                                                 # [B,S]


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _remat_wrap(body, remat):
    """remat: True (full), False (none), or "dots" (save matmul outputs —
    selective checkpointing; recompute only the cheap elementwise chain)."""
    if remat is True:
        return jax.checkpoint(body)
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    return body


def _dense_body(cfg, ctx, remat=True):
    def body(lp, x):
        h, _ = L.attention_block(lp["attn"],
                                 L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                                 cfg, ctx)
        x = x + h
        x = x + L.mlp_block(lp["mlp"],
                            L.rms_norm(x, lp["ln2"], cfg.norm_eps), ctx)
        return x, jnp.zeros((), jnp.float32)
    return _remat_wrap(body, remat)


def _moe_body(cfg, ctx, remat=True):
    def body(lp, x):
        h, _ = L.attention_block(lp["attn"],
                                 L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                                 cfg, ctx)
        x = x + h
        y, aux = M.moe_block(lp["moe"],
                             L.rms_norm(x, lp["ln2"], cfg.norm_eps), cfg, ctx)
        return x + y, aux
    return _remat_wrap(body, remat)


def _ssm_body(cfg, ctx, remat=True):
    def body(lp, x):
        h, _ = S.ssm_block(lp["ssm"],
                           L.rms_norm(x, lp["ln"], cfg.norm_eps), cfg, ctx)
        return x + h, jnp.zeros((), jnp.float32)
    return _remat_wrap(body, remat)


def _scan_blocks(stacked, x, body):
    def step(carry, lp):
        x, aux = carry
        x, a = body(lp, x)
        return (x, aux + a), None
    (x, aux), _ = lax.scan(step, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


def _hybrid_forward(p, x, cfg, ctx, remat=True):
    """Zamba2: mamba backbone, shared attn block every `attn_every` layers.

    Grouped scan: each scan step applies `attn_every` mamba layers (inner
    stacked slice) then the SHARED attention block (same weights each time).
    Remainder layers run in a second scan without attention."""
    k = cfg.hybrid.attn_every
    n = cfg.n_layers
    g, rem = divmod(n, k)
    mamba_body = _ssm_body(cfg, ctx, remat)
    dense_body = _dense_body(cfg, ctx, remat)
    grouped = jax.tree.map(
        lambda a: a[: g * k].reshape((g, k) + a.shape[1:]), p["layers"])
    rest = jax.tree.map(lambda a: a[g * k:], p["layers"])

    def group_step(carry, glp):
        x, aux = carry
        x, a = _scan_blocks(glp, x, mamba_body)
        x, a2 = dense_body(p["shared_attn"], x)
        return (x, aux + a + a2), None

    (x, aux), _ = lax.scan(group_step, (x, jnp.zeros((), jnp.float32)),
                           grouped)
    if rem:
        x, a = _scan_blocks(rest, x, mamba_body)
        aux = aux + a
    return x, aux


def _encoder_forward(p, enc_embed, cfg, ctx, remat=True):
    """Whisper encoder: bidirectional attention over frame embeddings."""
    def body(lp, x):
        h, _ = L.attention_block(lp["attn"],
                                 L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                                 cfg, ctx, causal=False)
        x = x + h
        x = x + L.mlp_block(lp["mlp"],
                            L.rms_norm(x, lp["ln2"], cfg.norm_eps), ctx)
        return x, jnp.zeros((), jnp.float32)
    body = jax.checkpoint(body) if remat else body
    x, _ = _scan_blocks(p["enc_layers"], enc_embed, body)
    return L.rms_norm(x, p["enc_norm"], cfg.norm_eps)


def _decoder_body(cfg, ctx, remat=True):
    """Whisper decoder block: self-attn + cross-attn + mlp.

    The cross-attention K/V are computed from the encoder output inside the
    block (global shapes carry enc output, per-layer xattn weights)."""
    def body(lp, carry):
        x, enc = carry
        h, _ = L.attention_block(lp["attn"],
                                 L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                                 cfg, ctx, causal=True)
        x = x + h
        # cross-attention: queries from x, keys/values from enc
        xn = L.rms_norm(x, lp["ln_x"], cfg.norm_eps)
        kv = _xattn_kv(lp["xattn"], enc, cfg, ctx)
        h, _ = L.attention_block(lp["xattn"], xn, cfg, ctx, xattn_kv=kv)
        x = x + h
        x = x + L.mlp_block(lp["mlp"],
                            L.rms_norm(x, lp["ln2"], cfg.norm_eps), ctx)
        return (x, enc), jnp.zeros((), jnp.float32)
    return _remat_wrap(body, remat)


def _xattn_kv(ap, enc, cfg, ctx):
    b, se, d = enc.shape
    hd = cfg.head_dim_
    _, kv_w, _ = L.head_layout(cfg, ctx)
    wk, bk = L._kv_slice(ap, cfg, ctx, "k")
    wv, bv = L._kv_slice(ap, cfg, ctx, "v")
    k = jnp.einsum("bsd,df->bsf", enc, wk)
    v = jnp.einsum("bsd,df->bsf", enc, wv)
    if bk is not None:
        k, v = k + bk, v + bv
    return k.reshape(b, se, kv_w, hd), v.reshape(b, se, kv_w, hd)


def forward(p, tokens: jax.Array, cfg: ArchConfig, ctx: ParallelCtx, *,
            vis_embed=None, enc_embed=None, remat: bool = True):
    """Train/prefill forward -> (hidden [B,S,D], aux_loss scalar)."""
    x = embed_tokens(p, tokens, cfg, ctx)
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    if fam == "vlm":
        assert vis_embed is not None, "vlm needs stub patch embeddings"
        x = jnp.concatenate([vis_embed.astype(x.dtype), x], axis=1)
    if fam in ("dense", "vlm"):
        x, aux = _scan_blocks(p["layers"], x, _dense_body(cfg, ctx, remat))
    elif fam == "moe":
        if "prefix" in p:
            x, _ = _scan_blocks(p["prefix"], x, _dense_body(cfg, ctx, remat))
        x, aux = _scan_blocks(p["layers"], x, _moe_body(cfg, ctx, remat))
    elif fam == "ssm":
        x, aux = _scan_blocks(p["layers"], x, _ssm_body(cfg, ctx, remat))
    elif fam == "hybrid":
        x, aux = _hybrid_forward(p, x, cfg, ctx, remat)
    elif fam == "encdec":
        assert enc_embed is not None, "encdec needs stub frame embeddings"
        enc = _encoder_forward(p, enc_embed.astype(x.dtype), cfg, ctx, remat)
        # scan decoder blocks with the encoder output carried alongside
        body = _decoder_body(cfg, ctx, remat)

        def step(carry, lp):
            (x, enc, aux) = carry
            (x, enc), a = body(lp, (x, enc))
            return (x, enc, aux + a), None
        (x, enc, aux), _ = lax.scan(
            step, (x, enc, jnp.zeros((), jnp.float32)), p["layers"])
    else:
        raise ValueError(fam)
    if fam == "vlm":
        x = x[:, vis_embed.shape[1]:]
    return L.rms_norm(x, p["final_norm"], cfg.norm_eps), aux


def lm_loss(p, batch: Dict[str, jax.Array], cfg: ArchConfig,
            ctx: ParallelCtx, *, remat: bool = True):
    """Mean next-token NLL (+ MoE aux) over the local batch shard."""
    x, aux = forward(p, batch["tokens"], cfg, ctx,
                     vis_embed=batch.get("vis_embed"),
                     enc_embed=batch.get("enc_embed"), remat=remat)
    logits_l = lm_logits_local(p, x, cfg, ctx)
    nll = vocab_parallel_xent(logits_l, batch["labels"], ctx, cfg.vocab)
    loss = nll.mean()
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodeConfig:
    """Static decode-shape parameters.

    cache_len_local : per-shard sequence slice of the KV cache
    seq_shard       : None (cache local) | "model" | "model_data"
    window_override : "cfg" or an int/None — the --swa-override variant
    """
    cache_len_local: int
    seq_shard: Optional[str] = "model"
    window_override: Any = "cfg"


def init_cache(cfg: ArchConfig, ctx: ParallelCtx, dcfg: DecodeConfig,
               batch_local: int, dtype=None):
    """Zero cache pytree (local shapes — build under shard_map or use
    cache_specs for the global view)."""
    if cfg.mla is not None:
        raise ValueError(
            f"{cfg.name}: latent attention (MLA) serves through "
            f"PagedServeEngine, whose pool holds the latent; there is no "
            f"dense K/V cache for it")
    dtype = dtype or cfg.dtype
    hd = cfg.head_dim_
    fam = cfg.family
    sl = dcfg.cache_len_local
    if fam in ("dense", "vlm", "moe", "encdec"):
        kv_w = cfg.n_kv_heads if dcfg.seq_shard is not None \
            else L.head_layout(cfg, ctx)[1]
        n = cfg.n_layers
        kv = lambda: jnp.zeros((n, batch_local, sl, kv_w, hd), dtype)
        cache = {"k": kv(), "v": kv()}
        if fam == "encdec":
            se = cfg.encdec.n_frames
            kv_x = L.head_layout(cfg, ctx)[1]   # cross-attn: local heads
            cache["xk"] = jnp.zeros((n, batch_local, se, kv_x, hd), dtype)
            cache["xv"] = jnp.zeros((n, batch_local, se, kv_x, hd), dtype)
        return cache
    if fam == "ssm":
        return _ssm_cache(cfg, ctx, batch_local, dtype)
    if fam == "hybrid":
        c = _ssm_cache(cfg, ctx, batch_local, dtype)
        g = cfg.n_layers // cfg.hybrid.attn_every
        kv_w = cfg.n_kv_heads if dcfg.seq_shard is not None \
            else L.head_layout(cfg, ctx)[1]
        c["attn_k"] = jnp.zeros((g, batch_local, sl, kv_w, hd), dtype)
        c["attn_v"] = jnp.zeros((g, batch_local, sl, kv_w, hd), dtype)
        return c
    raise ValueError(fam)


def _ssm_cache(cfg, ctx, batch_local, dtype):
    ssm = cfg.ssm
    tp = max(ctx.tp_size, 1)
    h_l = ssm.n_heads(cfg.d_model) // tp if tp > 1 \
        else ssm.n_heads(cfg.d_model)
    d_in_l = h_l * ssm.head_dim
    return {
        "ssm": jnp.zeros((cfg.n_layers, batch_local, h_l, ssm.d_state,
                          ssm.head_dim), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch_local,
                           ssm.conv_kernel - 1, d_in_l), dtype),
    }


@dataclasses.dataclass(frozen=True)
class PagedConfig:
    """Static paged-serving shape parameters (DESIGN.md §13).

    block_size         : tokens per physical KV block
    n_blocks           : physical blocks in the pool (per layer)
    max_blocks_per_req : logical blocks per request row
                         (= ceil(request length cap / block_size))
    attn_impl          : "reference" (chunked K/V gather in the streaming
                         softmax, bit-identical to the wave path) |
                         "kernel" (flash_decode Pallas)
    window_override    : "cfg" or an int/None, as DecodeConfig
    """
    block_size: int = 16
    n_blocks: int = 64
    max_blocks_per_req: int = 8
    attn_impl: str = "reference"
    window_override: Any = "cfg"


PAGED_FAMILIES = ("dense", "vlm", "moe")


def init_paged_pool(cfg: ArchConfig, ctx: ParallelCtx, pcfg: PagedConfig,
                    dtype=None):
    """Zero paged KV pool: ``[L, n_blocks, block_size, kv_w, hd]`` per K
    and V, or under latent attention one latent pool ``[L, n_blocks,
    block_size, kv_lora_rank + qk_rope_head_dim]`` (``"latent"``).
    ``pool_width`` is the latent width rounded up to the TPU's 128
    lanes, where a 576-wide row lies in 640 in any layout that keeps
    positions as rows; the pad columns stay zero.  Block contents are
    never zeroed again — reuse relies on kv_valid masking
    (serving/paged_kv.py)."""
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged serving supports {PAGED_FAMILIES}, got {cfg.family} "
            f"(ssm/hybrid/encdec stay on the wave engine)")
    dtype = dtype or cfg.dtype
    if cfg.mla is not None:
        return {"latent": jnp.zeros((cfg.n_layers, pcfg.n_blocks,
                                     pcfg.block_size, cfg.mla.pool_width),
                                    dtype)}
    kv_w = L.head_layout(cfg, ctx)[1]
    shape = (cfg.n_layers, pcfg.n_blocks, pcfg.block_size, kv_w,
             cfg.head_dim_)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_decode_step(p, pool, tokens: jax.Array, positions: jax.Array,
                      row_req: jax.Array, block_tables: jax.Array,
                      sample_rows: jax.Array, cfg: ArchConfig,
                      ctx: ParallelCtx, pcfg: PagedConfig):
    """One packed continuous-batching step (context + generation phases).

    pool                     : init_paged_pool's dict, every layer stacked:
        ``{"k", "v"}`` or ``{"latent"}``; its keys pick the attention
        sublayer (layers.paged_attention_block or mla_paged_block)
    tokens/positions/row_req : [T] int32 — packed rows; ``row_req`` maps a
        row to its request row (block-table row), -1 for bucket padding
    block_tables             : [R, max_blocks_per_req] int32
    sample_rows              : [R] int32 — packed index of each request
        row's sequence-frontier row (engine ignores logits of rows that
        sampled nothing this tick)

    Returns (logits [R, V_local], new pool); the MoE family returns a
    third value, int32 ``[n_held + 1]``: the real rows' token-expert pairs
    on each held expert, then all their pairs, summed over the expert
    layers (moe.route_counts).  Padding rows cost zero attention mass and
    zero pool writes.  One layer loop serves both kinds of pool: it
    carries the whole stacked pool, and each layer writes and reads its
    own blocks in place, so a caller that donates ``pool``
    (serving/engine.paged_step_builder) gets it updated in place.
    Named scopes tag the compiled ops for the device trace: ``embed``;
    ``layers`` around the layer loop, inside it the attention sublayer's
    own (``qkv_proj``, ``attn/...``, ``o_proj``; under latent attention
    ``mla_q``, ``mla_kv``, ``attn/...``, ``mla_out``) and ``mlp`` (or
    ``moe`` with ``route``, ``experts``, ``shared``); ``head``.
    """
    fam = cfg.family
    if fam not in PAGED_FAMILIES:
        raise ValueError(fam)
    if cfg.mla is not None and pcfg.attn_impl != "reference":
        raise ValueError("latent attention runs the reference paged path; "
                         "the flash-decode kernel reads K/V pools")
    valid = row_req >= 0
    n_req = block_tables.shape[0]
    with jax.named_scope("attn"):
        btab = block_tables[jnp.clip(row_req, 0, n_req - 1)]  # [T, maxb]
        kv_valid = jnp.where(valid, positions + 1, 0)
    with jax.named_scope("embed"):
        x = embed_tokens(p, tokens[:, None], cfg, ctx)       # [T, 1, D]

    def attn(lp, x, pool, layer):
        # the attention sublayer of the pool's kind: K/V or latent
        if "latent" in pool:
            with jax.named_scope("mla_q"):
                h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            h, latent = L.mla_paged_block(
                lp["attn"], h, cfg, ctx, positions=positions,
                kv_valid=kv_valid, pool=pool["latent"], layer=layer,
                block_tables=btab)
            return x + h, {"latent": latent}
        with jax.named_scope("qkv_proj"):
            h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, (k, v) = L.paged_attention_block(
            lp["attn"], h, cfg, ctx, positions=positions, kv_valid=kv_valid,
            pools=(pool["k"], pool["v"]), layer=layer, block_tables=btab,
            window_override=pcfg.window_override, impl=pcfg.attn_impl)
        return x + h, {"k": k, "v": v}

    def mlp(lp, x):
        with jax.named_scope("mlp"):
            return x + L.mlp_block(
                lp["mlp"], L.rms_norm(x, lp["ln2"], cfg.norm_eps), ctx)

    counts = jnp.zeros((cfg.moe.n_held + 1,), jnp.int32) \
        if fam == "moe" else None

    def step(carry, inp):
        x, pool, counts = carry
        lp, layer = inp
        x, pool = attn(lp, x, pool, layer)
        if "mlp" in lp:
            return (mlp(lp, x), pool, counts), None
        with jax.named_scope("moe"):
            y, _, c = M.moe_layer(
                lp["moe"], L.rms_norm(x, lp["ln2"], cfg.norm_eps), cfg, ctx,
                valid=valid)
        return (x + y, pool, counts + c), None

    # "layers": the layer loop's own ops.  The loop carries the whole
    # stacked pool and each layer writes and reads its own blocks in
    # place, so no layer's pool is sliced out or written back.
    npre = cfg.moe.n_dense_prefix if "prefix" in p else 0
    with jax.named_scope("layers"):
        for i in range(npre):
            lp = jax.tree.map(lambda a: a[i], p["prefix"])
            x, pool = attn(lp, x, pool, i)
            x = mlp(lp, x)
        (x, pool, counts), _ = lax.scan(
            step, (x, pool, counts),
            (p["layers"], jnp.arange(npre, cfg.n_layers)))
    with jax.named_scope("head"):
        logits_l = _paged_head(p, x, sample_rows, cfg, ctx)
    if counts is None:
        return logits_l, pool
    return logits_l, pool, counts


def _paged_head(p, x, sample_rows, cfg, ctx):
    x = L.rms_norm(x, p["final_norm"], cfg.norm_eps)
    xs = x[jnp.clip(sample_rows, 0, x.shape[0] - 1)]         # [R, 1, D]
    return lm_logits_local(p, xs, cfg, ctx)[:, 0]            # [R, V_l]


def decode_step(p, cache, token: jax.Array, pos: jax.Array,
                cfg: ArchConfig, ctx: ParallelCtx, dcfg: DecodeConfig,
                enc_out=None):
    """One decode step: token [B,1] int32, pos scalar -> (logits [B,V_l],
    new cache).  Caches are sequence-sharded per dcfg.seq_shard."""
    x = embed_tokens(p, token, cfg, ctx)
    fam = cfg.family
    pos_arr = jnp.asarray(pos)
    if pos_arr.ndim:                              # per-slot positions [B]
        positions = pos_arr[:, None] + jnp.arange(token.shape[1])
    else:
        positions = pos + jnp.arange(token.shape[1])

    def attn_cached(lp, x, kv, g_idx=None):
        h, new_kv = L.attention_block(
            lp["attn"], L.rms_norm(x, lp["ln1"], cfg.norm_eps), cfg, ctx,
            positions=positions, kv_cache=kv, cache_pos=pos,
            seq_shard=dcfg.seq_shard, window_override=dcfg.window_override)
        return x + h, new_kv

    if fam in ("dense", "vlm", "moe"):
        def step(x, inp):
            lp, ck, cv = inp
            x, (nk, nv) = attn_cached(lp, x, (ck, cv))
            if "mlp" in lp:
                x = x + L.mlp_block(
                    lp["mlp"], L.rms_norm(x, lp["ln2"], cfg.norm_eps), ctx)
            else:
                y, _ = M.moe_block(
                    lp["moe"], L.rms_norm(x, lp["ln2"], cfg.norm_eps),
                    cfg, ctx)
                x = x + y
            return x, (nk, nv)
        stacked = p["layers"]
        if fam == "moe" and "prefix" in p:
            npre = cfg.moe.n_dense_prefix
            for i in range(npre):
                lp = jax.tree.map(lambda a: a[i], p["prefix"])
                x, (nk, nv) = attn_cached(
                    lp, x, (cache["k"][i], cache["v"][i]))
                cache = dict(cache)
                cache["k"] = cache["k"].at[i].set(nk)
                cache["v"] = cache["v"].at[i].set(nv)
                x = x + L.mlp_block(
                    lp["mlp"], L.rms_norm(x, lp["ln2"], cfg.norm_eps), ctx)
            body_k = cache["k"][npre:]
            body_v = cache["v"][npre:]
            x, (nk, nv) = lax.scan(step, x, (stacked, body_k, body_v))
            cache["k"] = cache["k"].at[npre:].set(nk)
            cache["v"] = cache["v"].at[npre:].set(nv)
        else:
            x, (nk, nv) = lax.scan(step, x, (stacked, cache["k"],
                                             cache["v"]))
            cache = {"k": nk, "v": nv}
    elif fam == "encdec":
        def step(x, inp):
            lp, ck, cv, xk, xv = inp
            x, (nk, nv) = attn_cached(lp, x, (ck, cv))
            xn = L.rms_norm(x, lp["ln_x"], cfg.norm_eps)
            h, _ = L.attention_block(lp["xattn"], xn, cfg, ctx,
                                     xattn_kv=(xk, xv))
            x = x + h
            x = x + L.mlp_block(lp["mlp"],
                                L.rms_norm(x, lp["ln2"], cfg.norm_eps), ctx)
            return x, (nk, nv)
        x, (nk, nv) = lax.scan(step, x, (p["layers"], cache["k"], cache["v"],
                                         cache["xk"], cache["xv"]))
        cache = dict(cache, k=nk, v=nv)
    elif fam == "ssm":
        def step(x, inp):
            lp, s_ssm, s_conv = inp
            h, ns = S.ssm_block(lp["ssm"],
                                L.rms_norm(x, lp["ln"], cfg.norm_eps),
                                cfg, ctx,
                                state={"ssm": s_ssm, "conv": s_conv})
            return x + h, (ns["ssm"], ns["conv"])
        x, (ns, nc) = lax.scan(step, x, (p["layers"], cache["ssm"],
                                         cache["conv"]))
        cache = {"ssm": ns, "conv": nc}
    elif fam == "hybrid":
        k = cfg.hybrid.attn_every
        g = cfg.n_layers // k
        grouped = jax.tree.map(
            lambda a: a[: g * k].reshape((g, k) + a.shape[1:]), p["layers"])
        g_ssm = cache["ssm"][: g * k].reshape((g, k) + cache["ssm"].shape[1:])
        g_conv = cache["conv"][: g * k].reshape(
            (g, k) + cache["conv"].shape[1:])

        def mamba_step(x, inp):
            lp, s_ssm, s_conv = inp
            h, ns = S.ssm_block(lp["ssm"],
                                L.rms_norm(x, lp["ln"], cfg.norm_eps),
                                cfg, ctx,
                                state={"ssm": s_ssm, "conv": s_conv})
            return x + h, (ns["ssm"], ns["conv"])

        def group_step(x, inp):
            glp, s_ssm, s_conv, ak, av = inp
            x, (ns, nc) = lax.scan(mamba_step, x, (glp, s_ssm, s_conv))
            sp = p["shared_attn"]
            h, (nak, nav) = L.attention_block(
                sp["attn"], L.rms_norm(x, sp["ln1"], cfg.norm_eps), cfg, ctx,
                positions=positions, kv_cache=(ak, av), cache_pos=pos,
                seq_shard=dcfg.seq_shard,
                window_override=dcfg.window_override)
            x = x + h
            x = x + L.mlp_block(sp["mlp"],
                                L.rms_norm(x, sp["ln2"], cfg.norm_eps), ctx)
            return x, (ns, nc, nak, nav)

        x, (ns, nc, nak, nav) = lax.scan(
            group_step, x, (grouped, g_ssm, g_conv, cache["attn_k"],
                            cache["attn_v"]))
        cache = dict(cache)
        cache["ssm"] = cache["ssm"].at[: g * k].set(
            ns.reshape((g * k,) + ns.shape[2:]))
        cache["conv"] = cache["conv"].at[: g * k].set(
            nc.reshape((g * k,) + nc.shape[2:]))
        cache["attn_k"], cache["attn_v"] = nak, nav
        rem = cfg.n_layers - g * k
        if rem:
            rest = jax.tree.map(lambda a: a[g * k:], p["layers"])
            x, (ns2, nc2) = lax.scan(
                mamba_step, x, (rest, cache["ssm"][g * k:],
                                cache["conv"][g * k:]))
            cache["ssm"] = cache["ssm"].at[g * k:].set(ns2)
            cache["conv"] = cache["conv"].at[g * k:].set(nc2)
    else:
        raise ValueError(fam)

    x = L.rms_norm(x, p["final_norm"], cfg.norm_eps)
    logits_l = lm_logits_local(p, x[:, -1:], cfg, ctx)[:, 0]
    return logits_l, cache
