"""Kimi K2 (Kimi-K2-Instruct) — the DeepSeek-V3 block: latent attention
(MLA) in all 61 layers, layer 0 a dense SwiGLU of 18432, layers 1-60 an
expert layer of 384 routed experts of 2048 (8 per token, sigmoid scores
with a correction bias, renormalised and scaled by 2.827) beside one
shared expert of 2048; rotary positions with YaRN (factor 32).
https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json
[arXiv:2412.19437]"""

from repro.models.config import ArchConfig, MLAConfig, MoEConfig, YarnConfig

CONFIG = ArchConfig(
    name="kimi-k2-1t-a32b", family="moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=64,
    d_ff=18432, vocab=163840, rope_theta=50000.0, norm_eps=1e-6,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YarnConfig(factor=32.0, original_max_position=4096,
                            beta_fast=1.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    # 384 experts shard over the expert-parallel span with all_to_all
    # dispatch + TP inside each expert (models/moe.py ep_a2a); a device
    # outside any such span computes the part of the experts it holds.
    moe=MoEConfig(n_experts=384, top_k=8, n_dense_prefix=1, impl="ep_a2a",
                  d_expert=2048, n_shared=1, scoring="sigmoid",
                  routed_scale=2.827),
    source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
           "config.json [arXiv:2412.19437]",
)
