"""Per-architecture smoke tests (required deliverable f).

Each assigned arch instantiates its REDUCED variant (2 layers,
d_model<=512, <=4 experts) and runs one forward + one train step on CPU,
asserting output shapes and the absence of NaNs.  The FULL configs are
exercised by the dry-run only.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import make_batches
from repro.models import (init_params, lm_loss, forward,
                          single_device_ctx)
from repro.models.transformer import lm_logits_local
from repro.optim.adamw import AdamWConfig, apply_updates, init_state

CTX = single_device_ctx()
KEY = jax.random.PRNGKey(0)


def reduced(arch):
    cfg = get_config(arch).reduced()
    cfg.validate()
    return cfg


def make_batch(cfg, b=2, s=16):
    it = make_batches(cfg, seq_len=s, batch_per_shard=b, seed=3)
    batch = next(it)
    return {k: jnp.asarray(v) for k, v in batch.items()}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The full config carries the exact assigned dimensions."""
    cfg = get_config(arch)
    expect = {
        "mixtral_8x7b": (32, 4096, 32, 8, 14336, 32000),
        "internvl2_76b": (80, 8192, 64, 8, 28672, 128256),
        "kimi_k2_1t_a32b": (61, 7168, 64, 64, 18432, 163840),
        "deepseek_67b": (95, 8192, 64, 8, 22016, 102400),
        "starcoder2_15b": (40, 6144, 48, 4, 24576, 49152),
        "whisper_medium": (24, 1024, 16, 16, 4096, 51865),
        "mamba2_1p3b": (48, 2048, 0, 0, 0, 50280),
        "zamba2_1p2b": (38, 2048, 32, 32, 8192, 32000),
        "qwen2_72b": (80, 8192, 64, 8, 29568, 152064),
        "glm4_9b": (40, 4096, 32, 2, 13696, 151552),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expect
    assert cfg.source  # citation present


def test_arch_extras():
    assert get_config("mixtral_8x7b").moe.n_experts == 8
    assert get_config("mixtral_8x7b").moe.top_k == 2
    assert get_config("mixtral_8x7b").sliding_window == 4096
    kimi = get_config("kimi_k2_1t_a32b")
    k2 = kimi.moe
    assert (k2.n_experts, k2.top_k) == (384, 8)
    assert (k2.n_dense_prefix, k2.d_expert, k2.n_shared) == (1, 2048, 1)
    assert (k2.scoring, k2.routed_scale, k2.held) == ("sigmoid", 2.827, None)
    m = kimi.mla
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (1536, 512, 128, 64, 128)
    y = kimi.rope_scaling
    assert (kimi.rope_theta, y.factor, y.original_max_position, y.beta_fast,
            y.beta_slow, y.mscale, y.mscale_all_dim) == \
        (50000.0, 32.0, 4096, 1.0, 1.0, 1.0, 1.0)
    assert kimi.norm_eps == 1e-6 and not kimi.tie_embeddings
    assert "arXiv:2412.19437" in kimi.source
    assert get_config("mamba2_1p3b").ssm.d_state == 128
    assert get_config("zamba2_1p2b").ssm.d_state == 64
    assert get_config("qwen2_72b").qkv_bias


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = reduced(arch)
    assert cfg.n_layers == 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    params = init_params(KEY, cfg, CTX)
    batch = make_batch(cfg)

    # forward: shapes + no NaN
    x, aux = forward(params, batch["tokens"], cfg, CTX,
                     vis_embed=batch.get("vis_embed"),
                     enc_embed=batch.get("enc_embed"), remat=False)
    assert x.shape == (2, 16, cfg.d_model)
    assert not bool(jnp.isnan(x).any())
    logits = lm_logits_local(params, x, cfg, CTX)
    assert logits.shape == (2, 16, cfg.vocab_padded)

    # one full train step: loss + grads + adamw update, all finite
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    opt_state = init_state(params)
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(p, batch, cfg, CTX, remat=True))(params)
    assert jnp.isfinite(loss)
    new_params, opt_state, metrics = apply_updates(params, grads, opt_state,
                                                   opt_cfg)
    assert jnp.isfinite(metrics["grad_norm"])
    for leaf in jax.tree.leaves(new_params):
        assert not bool(jnp.isnan(leaf).any())
    # parameters actually moved
    moved = any(
        float(jnp.abs(a - b).max()) > 0
        for a, b in zip(jax.tree.leaves(new_params),
                        jax.tree.leaves(params)))
    assert moved
