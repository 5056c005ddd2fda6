"""Latent attention (MLA) and sigmoid-routed experts with a shared expert
and a held share of the experts (the Kimi K2 block), against the plain
reference ``chipbench/mla_moe/reference.py`` on seeded random float32
weights at a tiny size: d 64, 4 heads, ranks 32/16, head dims 16/8/16,
16 routed experts of which 4 are held, top-4, one shared expert, one
dense and two expert layers, YaRN on.

Tolerance: the program and the reference both compute in float32 here
(on the CPU a float32 product is exact float32), and differ only in the
order of sums (absorbed against decompressed attention, streaming against
whole softmax, packed rows, per-expert sums), so logits agree to a few
float32 ulps of their scale; ``ATOL`` 2e-4 on logits of about 0.1-1
leaves ten times that room.  Each fault (correction bias zeroed, YaRN's
attention scale left out) moves some logits by more than ten times
``ATOL``.
"""

import copy
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench.drivers.serve_mla_moe import program_config  # noqa: E402
from chipbench.mla_moe import check as C  # noqa: E402
from chipbench.mla_moe import reference as R  # noqa: E402
from chipbench.mla_moe import weights as W  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.models import single_device_ctx  # noqa: E402
from repro.models.transformer import (forward, init_params,  # noqa: E402
                                      lm_logits_local)
from repro.serving.engine import (PagedServeConfig,  # noqa: E402
                                  PagedServeEngine)

CTX = single_device_ctx()
ATOL = 2e-4
SEED = 8589934597

KIMI = json.loads((ROOT / "chipbench/configs/kimi-k2.json").read_text())
TINY = dict(KIMI, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=4, experts_first=4,
            experts_held=4, num_hidden_layers=3, vocab_size=256,
            param_dtype="float32")


def tiny_model(**over):
    a = dict(TINY, **over)
    cfg = program_config(a)
    return a, cfg, W.make(a, SEED)


def served(eng, prompts, max_new):
    """Serve each prompt through ``eng`` one after another; the logits of
    every sampled row, per request."""
    got = []
    real = eng._sample
    eng._sample = lambda logits, t: (got[-1].append(np.array(logits)),
                                     real(logits, t))[1]
    outs = []
    for prompt in prompts:
        got.append([])
        rid = eng.submit(prompt, max_new=max_new)
        eng.run_until_drained()
        outs.append(eng.finished()[rid])
    return outs, [np.stack(g) for g in got]


def test_paged_engine_matches_reference():
    """Prefill in chunks of 8 rows, then decode through the latent pool
    (blocks of 4, reused by the second request), against the reference's
    full forward logits at every served position."""
    a, cfg, params = tiny_model()
    eng = PagedServeEngine(params, cfg, CTX, PagedServeConfig(
        max_requests=2, cache_len=64, kv_block=4, n_blocks=16,
        max_tokens_in_flight=8, min_bucket=4))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, n).tolist() for n in (19, 11)]
    outs, logits = served(eng, prompts, max_new=6)
    eng.close()
    for prompt, out, got in zip(prompts, outs, logits):
        seq = prompt + out
        want = np.asarray(R.logits(params, seq[:-1], a))[len(prompt) - 1:]
        assert got.shape == want.shape == (6, 256)
        np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_forward_matches_reference():
    a, cfg, params = tiny_model()
    toks = np.random.default_rng(4).integers(0, 256, (2, 24))
    x, _ = forward(params, jnp.asarray(toks), cfg, CTX, remat=False)
    got = np.asarray(lm_logits_local(params, x, cfg, CTX))
    for b in range(2):
        want = np.asarray(R.logits(params, toks[b], a))
        np.testing.assert_allclose(got[b], want, atol=ATOL, rtol=0)


def test_program_tree_is_the_benchmarks():
    """The program's own parameter tree has the benchmark weights' paths,
    shapes and types."""
    a, cfg, params = tiny_model()
    mine = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda x: (x.shape, str(x.dtype)), t)
    assert sds(mine) == sds(W.abstract(a))


def _moe_input(a, n=40, seed=5):
    """Expert-layer weights of every expert (16) and an input [n, D]."""
    full = dict(a, experts_first=0, experts_held=a["n_routed_experts"])
    params = W.make(full, SEED)
    mp = jax.tree.map(lambda t: t[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, a["hidden_size"]))
    return full, mp, x


def _held(mp, first, count):
    return dict(mp, experts=jax.tree.map(lambda t: t[first:first + count],
                                         mp["experts"]))


def test_expert_shares_add_up_to_the_uncut_layer():
    """The held parts of four 4-expert shares, with the shared expert
    counted once, add up to the whole layer; and the program's layer
    holding one share computes that share (plus the shared expert)."""
    a, mp, x = _moe_input(TINY)
    whole = R.moe(x, mp, a, "f32", held=(0, 16))
    parts = sum(R.moe(x, _held(mp, f, 4), a, "f32", held=(f, 4),
                      shared=(f == 0)) for f in (0, 4, 8, 12))
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5, rtol=0)
    cfg = program_config(dict(TINY, experts_first=8))
    y, _, counts = M.moe_layer(_held(mp, 8, 4), x[None], cfg, CTX)
    want = R.moe(x, _held(mp, 8, 4), a, "f32", held=(8, 4))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               atol=1e-5, rtol=0)
    idx, _ = R.route(x, mp, a, "f32")
    idx = np.asarray(idx)
    assert list(np.asarray(counts)) == \
        [int((idx == e).sum()) for e in range(8, 12)] + [40 * 4]


def test_no_token_dropped_when_every_token_routes_to_one_held_expert():
    """A bias that sends all 64 tokens to held expert 5: a capacity of
    ceil(64 * 4 / 16 * 1.25) = 20 would drop 44 of them; the held layer
    computes every one."""
    a, mp, x = _moe_input(TINY, n=64)
    mp = dict(mp, router_bias=mp["router_bias"].at[5].set(100.0))
    cfg = program_config(TINY)
    assert M.capacity_of(64, cfg.moe) == 20
    y, _, counts = M.moe_layer(_held(mp, 4, 4), x[None], cfg, CTX)
    want = R.moe(x, _held(mp, 4, 4), a, "f32", held=(4, 4))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               atol=1e-5, rtol=0)
    assert int(counts[1]) == 64                       # expert 5 of 4..7


def test_held_routing_margin_by_hand():
    """The reference's held-expert margin against numpy in float64: a
    chosen held expert's lead over the 5th of ``scores + bias``, an
    unchosen one's shortfall below the 4th, the least over experts 4-7;
    and its choices are the router's."""
    a, mp, x = _moe_input(TINY, n=24)
    margin, chosen = R.held_routing(x, mp, a, held=(4, 4))
    logits = np.asarray(x, np.float64) @ np.asarray(mp["w_router"],
                                                    np.float64)
    v = 1 / (1 + np.exp(-logits)) + np.asarray(mp["router_bias"], np.float64)
    top = -np.sort(-v, axis=-1)
    last, left_out = top[:, 3:4], top[:, 4:5]
    vh = v[:, 4:8]
    assert (np.asarray(chosen) == (vh >= last)).all()
    np.testing.assert_allclose(
        np.asarray(margin),
        np.where(vh >= last, vh - left_out, last - vh).min(-1),
        atol=1e-6, rtol=0)
    idx = np.asarray(R.route(x, mp, a, "f32")[0])
    assert (np.asarray(chosen) ==
            (idx[:, :, None] == np.arange(4, 8)).any(1)).all()


def test_check_reads_only_settled_positions():
    """Served through the engine, every served token is the reference's
    best (gap 0 at every margin).  A token altered at the most settled
    served position reads as its gap at the cell's ``route_margin``; one
    altered at the least settled position reads at margin 0 and not at
    any margin above its own."""
    a, cfg, params = tiny_model()
    eng = PagedServeEngine(params, cfg, CTX, PagedServeConfig(
        max_requests=2, cache_len=64, kv_block=4, max_tokens_in_flight=8,
        min_bucket=4))
    prompt = np.random.default_rng(8).integers(1, 256, 15).tolist()
    outs, _ = served(eng, [prompt], max_new=8)
    eng.close()
    rows = C.served_positions(a, params, [(prompt, outs[0])], pad=64)
    assert C.widest(rows, 0.0) < ATOL
    margin = rows[0]["margin"]
    route_margin = json.loads((ROOT / "chipbench/traffic/longctx.json")
                              .read_text())["check"]["route_margin"]
    for i, caught in ((int(margin.argmax()), route_margin),
                      (int(margin.argmin()), None)):
        bad = list(outs[0])
        bad[i] = (bad[i] + 1) % 256
        rows = C.served_positions(a, params, [(prompt, bad)], pad=64)
        gap = rows[0]["gap"][i]
        assert gap > 10 * ATOL
        if caught is not None:
            assert margin[i] >= caught
            assert C.widest(rows, caught) >= gap
        else:
            assert C.widest(rows, 0.0) >= gap
            assert C.widest(rows, float(rows[0]["margin"][i]) + 1e-6) < gap


def _forward_gap(cfg, program_params, params, a):
    toks = np.random.default_rng(6).integers(0, 256, 32)
    x, _ = forward(program_params, jnp.asarray(toks[None]), cfg, CTX,
                   remat=False)
    got = np.asarray(lm_logits_local(program_params, x, cfg, CTX))[0]
    return float(np.abs(got - np.asarray(R.logits(params, toks, a))).max())


@pytest.mark.parametrize("fault", ["bias_zeroed", "yarn_scale_left_out"])
def test_faulty_program_fails_the_comparison(fault, monkeypatch):
    """A program that zeroes the correction bias, or leaves YaRN's
    attention factor out of the softmax scale, misses the reference by
    more than ten times the tolerance; the sound one is within it."""
    a, cfg, params = tiny_model()
    assert _forward_gap(cfg, params, params, a) < ATOL
    faulty = params
    if fault == "bias_zeroed":
        faulty = copy.deepcopy(params)
        moe = faulty["layers"]["moe"]
        moe["router_bias"] = jnp.zeros_like(moe["router_bias"])
    else:
        monkeypatch.setattr(L, "mla_softmax_scale",
                            lambda c: c.mla.qk_head_dim ** -0.5)
    assert _forward_gap(cfg, faulty, params, a) > 10 * ATOL


def test_yarn_at_kimis_published_settings():
    """Rope dim 64, theta 50000, factor 32, beta_fast = beta_slow = 1,
    4096 original positions: the correction dims are 19 and 20, so the
    first 20 frequencies are theta^(-2i/64), the other 12 those over 32;
    the softmax scale is 192^-0.5 (0.1 ln 32 + 1)^2 and cos and sin are
    not scaled (mscale = mscale_all_dim)."""
    kimi = get_config("kimi-k2-1t-a32b")
    i = np.arange(32)
    closed = 50000.0 ** (-2 * i / 64) / np.where(i >= 20, 32.0, 1.0)
    corr = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000))
    assert (math.floor(corr), math.ceil(corr)) == (19, 20)
    np.testing.assert_allclose(L.yarn_freqs(64, 50000.0, kimi.rope_scaling),
                               closed, rtol=1e-6)
    np.testing.assert_allclose(R.rope_freqs(KIMI), closed, rtol=1e-12)
    want = 192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2
    assert L.mla_softmax_scale(kimi) == pytest.approx(want, rel=1e-12)
    assert R.softmax_scale(KIMI) == pytest.approx(want, rel=1e-12)


def test_serving_report_counts_the_routing():
    """``serving_report()["moe"]`` against routing done by hand on the
    reference's hidden states: every position of the request but the
    last served one is one real row in each of the two expert layers."""
    a, cfg, params = tiny_model()
    eng = PagedServeEngine(params, cfg, CTX, PagedServeConfig(
        max_requests=2, cache_len=64, kv_block=4, max_tokens_in_flight=8,
        min_bucket=4))
    prompt = np.random.default_rng(7).integers(1, 256, 13).tolist()
    rid = eng.submit(prompt, max_new=5)
    eng.run_until_drained()
    rep = eng.serving_report()["moe"]
    eng.close()
    seq = prompt + eng.finished()[rid][:-1]
    x = R.embed(params, jnp.asarray(seq))
    per = np.zeros(4, int)
    for lp, dense in R.layers(params, a):
        if not dense:
            h = R.rms_norm(x + R.mla(R.rms_norm(x, lp["ln1"], 1e-6),
                                     lp["attn"], a, "f32"), lp["ln2"], 1e-6)
            idx = np.asarray(R.route(h, lp["moe"], a, "f32")[0])
            per += [(idx == e).sum() for e in range(4, 8)]
        x = R.layer(x, lp, a, "f32", dense)
    assert rep == {"assigned": len(seq) * 4 * 2, "held": int(per.sum()),
                   "per_expert": per.tolist()}


def test_wave_engine_and_dense_cache_name_the_paged_engine():
    from repro.serving.engine import ServeConfig, ServeEngine
    _, cfg, params = tiny_model()
    with pytest.raises(ValueError, match="PagedServeEngine"):
        ServeEngine(params, cfg, CTX, ServeConfig())


def test_reduced_kimi_keeps_its_mechanisms():
    cfg = get_config("kimi-k2-1t-a32b").reduced()
    assert cfg.mla is not None and cfg.rope_scaling is not None
    assert (cfg.moe.scoring, cfg.moe.n_shared, cfg.moe.held) == \
        ("sigmoid", 1, None)
    assert cfg.n_kv_heads == cfg.n_heads
