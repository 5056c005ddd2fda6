"""Model-engine tests: per-family forward, decode parity, SSD equivalence,
MoE dispatch properties, vocab-parallel loss vs dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import (ArchConfig, DecodeConfig, MoEConfig, SSMConfig,
                          HybridConfig, EncDecConfig, VLMConfig,
                          decode_step, forward, init_cache, init_params,
                          lm_loss, single_device_ctx)
from repro.models.transformer import lm_logits_local, vocab_parallel_xent
from repro.models import layers as L

CTX = single_device_ctx()
KEY = jax.random.PRNGKey(0)


def dense_cfg(**kw):
    d = dict(name="dense-t", family="dense", n_layers=2, d_model=64,
             n_heads=4, n_kv_heads=2, d_ff=128, vocab=97,
             param_dtype="float32")
    d.update(kw)
    return ArchConfig(**d)


FAMILY_CFGS = {
    "dense": dense_cfg(),
    "moe": dense_cfg(name="moe-t", family="moe",
                     moe=MoEConfig(n_experts=4, top_k=2, n_dense_prefix=1,
                                   impl="tp")),
    "ssm": dense_cfg(name="ssm-t", family="ssm", n_heads=0, n_kv_heads=0,
                     d_ff=0, ssm=SSMConfig(d_state=16, head_dim=16, chunk=8)),
    "hybrid": dense_cfg(name="hyb-t", family="hybrid", n_layers=3,
                        ssm=SSMConfig(d_state=16, head_dim=16, chunk=8),
                        hybrid=HybridConfig(attn_every=2)),
    "encdec": dense_cfg(name="enc-t", family="encdec", n_kv_heads=4,
                        encdec=EncDecConfig(n_enc_layers=2, n_frames=8)),
    "vlm": dense_cfg(name="vlm-t", family="vlm",
                     vlm=VLMConfig(n_vis_tokens=4)),
}


def make_batch(cfg, b=2, s=16):
    k1, k2 = jax.random.split(KEY)
    batch = {"tokens": jax.random.randint(k1, (b, s), 0, cfg.vocab),
             "labels": jax.random.randint(k2, (b, s), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["vis_embed"] = jnp.full((b, cfg.vlm.n_vis_tokens, cfg.d_model),
                                      0.1, jnp.float32)
    if cfg.family == "encdec":
        batch["enc_embed"] = jnp.full((b, cfg.encdec.n_frames, cfg.d_model),
                                      0.1, jnp.float32)
    return batch


@pytest.mark.parametrize("family", list(FAMILY_CFGS))
def test_forward_loss_finite(family):
    cfg = FAMILY_CFGS[family]
    p = init_params(KEY, cfg, CTX)
    loss = lm_loss(p, make_batch(cfg), cfg, CTX, remat=False)
    assert jnp.isfinite(loss)
    # random-init loss should be near ln(vocab)
    assert abs(float(loss) - np.log(cfg.vocab)) < 1.0


@pytest.mark.parametrize("family", list(FAMILY_CFGS))
def test_grads_finite(family):
    cfg = FAMILY_CFGS[family]
    p = init_params(KEY, cfg, CTX)
    g = jax.grad(lambda p: lm_loss(p, make_batch(cfg), cfg, CTX,
                                   remat=True))(p)
    leaves = jax.tree.leaves(g)
    assert all(jnp.all(jnp.isfinite(x)) for x in leaves)
    # at least some gradient signal everywhere except possibly aux scalars
    nonzero = sum(float(jnp.abs(x).sum()) > 0 for x in leaves)
    assert nonzero >= len(leaves) - 2


@pytest.mark.parametrize("family", ["dense", "ssm", "hybrid", "moe"])
def test_decode_matches_forward(family):
    """Teacher-forced decode step-by-step == full forward logits.

    For MoE the capacity factor is raised so no token is dropped — capacity
    drops legitimately differ between a 1-token decode call and a full-
    sequence forward (different per-call capacities)."""
    cfg = FAMILY_CFGS[family]
    if family == "moe":
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    p = init_params(KEY, cfg, CTX)
    b, s = 2, 12
    toks = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    x, _ = forward(p, toks, cfg, CTX, remat=False)
    full_logits = lm_logits_local(p, x, cfg, CTX)   # [B,S,V]

    dcfg = DecodeConfig(cache_len_local=s, seq_shard=None)
    cache = init_cache(cfg, CTX, dcfg, b)
    outs = []
    for t in range(s):
        lg, cache = decode_step(p, cache, toks[:, t:t + 1],
                                jnp.int32(t), cfg, CTX, dcfg)
        outs.append(lg)
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits),
                               rtol=2e-3, atol=2e-3)


def test_swa_masks_long_range():
    """Sliding-window attention must ignore tokens beyond the window."""
    cfg = dense_cfg(sliding_window=4)
    p = init_params(KEY, cfg, CTX)
    s = 16
    t1 = jax.random.randint(KEY, (1, s), 0, cfg.vocab)
    # perturb a token far outside the window of the last position
    t2 = t1.at[0, 2].set((t1[0, 2] + 1) % cfg.vocab)
    x1, _ = forward(p, t1, cfg, CTX, remat=False)
    x2, _ = forward(p, t2, cfg, CTX, remat=False)
    # last position attends only to positions >= 12 (window 4, 2 layers can
    # reach back 2*window); position 2 is out of reach
    np.testing.assert_allclose(np.asarray(x1[0, -1]), np.asarray(x2[0, -1]),
                               rtol=1e-5, atol=1e-5)


def test_causality():
    """Future tokens must not influence past logits."""
    cfg = dense_cfg()
    p = init_params(KEY, cfg, CTX)
    s = 10
    t1 = jax.random.randint(KEY, (1, s), 0, cfg.vocab)
    t2 = t1.at[0, -1].set((t1[0, -1] + 1) % cfg.vocab)
    x1, _ = forward(p, t1, cfg, CTX, remat=False)
    x2, _ = forward(p, t2, cfg, CTX, remat=False)
    np.testing.assert_allclose(np.asarray(x1[0, :-1]), np.asarray(x2[0, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_chunked_attention_matches_dense():
    """Streaming softmax == plain softmax attention."""
    b, s, h, hd = 2, 50, 4, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, 2, hd))
    v = jax.random.normal(ks[2], (b, s, 2, hd))
    out = L.chunked_attention(q, k, v, causal=True, chunk=16)
    # dense reference
    import math
    kk = jnp.repeat(k, 2, axis=2)
    vv = jnp.repeat(v, 2, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), vv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ssd_chunked_equals_naive_recurrence():
    from repro.models.ssm import _ssd_chunked
    b, s, h, hd, ds = 2, 37, 3, 8, 5
    ks = jax.random.split(KEY, 5)
    xh = jax.random.normal(ks[0], (b, s, h, hd))
    bt = jax.random.normal(ks[1], (b, s, ds)) * 0.5
    ct = jax.random.normal(ks[2], (b, s, ds)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[4], (h,)) * 0.2)
    y, s_fin = _ssd_chunked(xh, bt, ct, dt, a, chunk=8)
    st_ = jnp.zeros((b, h, ds, hd))
    ys = []
    for t in range(s):
        da = jnp.exp(dt[:, t] * a)
        st_ = st_ * da[:, :, None, None] + jnp.einsum(
            "bh,bs,bhd->bhsd", dt[:, t], bt[:, t], xh[:, t])
        ys.append(jnp.einsum("bs,bhsd->bhd", ct[:, t], st_))
    ref = jnp.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_fin), np.asarray(st_),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# MoE dispatch properties
# ---------------------------------------------------------------------------

@given(t=st.integers(4, 64), e=st.sampled_from([2, 4, 8]),
       cap=st.integers(1, 16), seed=st.integers(0, 100))
@settings(max_examples=30, deadline=None)
def test_property_dispatch_capacity(t, e, cap, seed):
    from repro.models.moe import dispatch_indices
    experts = jax.random.randint(jax.random.PRNGKey(seed), (t,), 0, e)
    slots, keep = dispatch_indices(experts, e, cap)
    slots = np.asarray(slots)
    keep = np.asarray(keep)
    # kept slots are unique and within their expert's capacity range
    kept = slots[keep]
    assert len(set(kept.tolist())) == len(kept)
    es = np.asarray(experts)[keep]
    assert ((kept >= es * cap) & (kept < (es + 1) * cap)).all()
    # per-expert kept count <= capacity
    for ee in range(e):
        assert (es == ee).sum() <= cap


def test_moe_combine_roundtrip():
    """dispatch -> identity expert -> combine reproduces kept tokens."""
    from repro.models.moe import (dispatch_indices, gather_to_buffers,
                                  combine_from_buffers)
    t, e, cap, d = 16, 4, 8, 8
    x = jax.random.normal(KEY, (t, d))
    experts = jax.random.randint(KEY, (t,), 0, e)
    slots, keep = dispatch_indices(experts, e, cap)
    buf = gather_to_buffers(x, slots, keep, e, cap)
    back = combine_from_buffers(buf, slots, keep, jnp.ones((t,)))
    got = np.asarray(back)
    want = np.where(np.asarray(keep)[:, None], np.asarray(x), 0.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_vocab_parallel_xent_matches_dense():
    b, s, v = 2, 6, 32
    logits = jax.random.normal(KEY, (b, s, v))
    labels = jax.random.randint(KEY, (b, s), 0, v)
    nll = vocab_parallel_xent(logits, labels, CTX, v)
    ref = -jax.nn.log_softmax(logits)[
        jnp.arange(b)[:, None], jnp.arange(s)[None], labels]
    np.testing.assert_allclose(np.asarray(nll), np.asarray(ref), rtol=1e-5)


@pytest.mark.parametrize("arch", ["glm4_9b", "qwen2_72b", "starcoder2_15b",
                                  "whisper_medium", "mixtral_8x7b",
                                  "internvl2_76b", "kimi_k2_1t_a32b",
                                  "deepseek_67b", "zamba2_1p2b"])
@pytest.mark.parametrize("tp", [1, 2, 4, 8, 16])
def test_head_layout_covers_all_assigned_configs(arch, tp):
    """The unified GQA sharding must be consistent for every assigned arch
    at every TP degree up to the production mesh: local Q heads x shards ==
    global heads, and each shard's KV slice covers its Q heads' groups."""
    from repro.configs import get_config
    from repro.models.layers import head_layout
    from repro.models.tp import ParallelCtx
    cfg = get_config(arch)
    if cfg.n_heads % tp:
        pytest.skip("tp does not divide heads")
    ctx = ParallelCtx(tp_size=tp, tp_axis="model" if tp > 1 else None)
    hq_l, kv_w, group_l = head_layout(cfg, ctx)
    assert hq_l * tp == cfg.n_heads
    assert hq_l == kv_w * group_l
    # every shard's Q-head range maps into a contiguous KV range of width
    # kv_w starting at its first KV head
    group = cfg.n_heads // cfg.n_kv_heads
    for shard in range(tp):
        q_heads = range(shard * hq_l, (shard + 1) * hq_l)
        kv_needed = {h // group for h in q_heads}
        first = (shard * hq_l * cfg.n_kv_heads) // cfg.n_heads
        assert kv_needed == set(range(first, first + len(kv_needed)))
        assert len(kv_needed) <= kv_w


@given(sq=st.integers(1, 40), skv=st.integers(1, 70),
       chunk=st.sampled_from([4, 16, 64]),
       causal=st.booleans(), window=st.sampled_from([None, 3, 8]))
@settings(max_examples=20, deadline=None)
def test_property_chunked_attention_vs_dense(sq, skv, chunk, causal, window):
    """Streaming softmax == dense softmax for random shapes/chunking/masks
    (self-attention case: kv and q lengths equal when causal)."""
    import math
    if causal:
        skv = sq
    b, h, hkv, hd = 1, 2, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(sq * 1000 + skv), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd))
    k = jax.random.normal(ks[1], (b, skv, hkv, hd))
    v = jax.random.normal(ks[2], (b, skv, hkv, hd))
    out = L.chunked_attention(q, k, v, causal=causal, window=window,
                              chunk=chunk)
    kk = jnp.repeat(k, h // hkv, axis=2)
    vv = jnp.repeat(v, h // hkv, axis=2)
    s_ = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(hd)
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s_ = jnp.where(mask[None, None], s_, -jnp.inf)
    p_ = jax.nn.softmax(s_, axis=-1)
    p_ = jnp.where(jnp.isnan(p_), 0.0, p_)   # fully-masked rows
    ref = jnp.einsum("bhqk,bkhd->bqhd", p_, vv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)
