"""The FLOP counter, the serving metric readers, the traffic generator and
the reference, at sizes a CPU holds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, reference as R, traffic as TR, weights as W
from chipbench.tests.tiny import TINY_ARCH

DS = {"n_layers": 4, "d_model": 8192, "n_heads": 64, "n_kv_heads": 8,
      "head_dim": 128, "d_ff": 22016, "vocab": 102400}


def test_layer_params_match_the_weights():
    spec = W.shapes(DS)
    per_layer = sum(np.prod(s[1:]) for k, (s, norm) in spec.items()
                    if k.startswith("layers/") and not norm)
    assert flops.layer_matmul_params(DS) == per_layer == 692_060_160


def test_serve_flops_counts_keys_and_sampled_heads():
    a = dict(DS, vocab=100)
    one = flops.serve_flops(a, [0], 0)
    assert one == 2 * 4 * 692_060_160 + 4 * 4 * 64 * 128 * 1
    assert flops.serve_flops(a, [0, 9], 1) == (
        2 * one - 4 * 4 * 64 * 128 + 4 * 4 * 64 * 128 * 10
        + 2 * 8192 * 100)


def test_serve_mfu_is_over_tick_time_not_the_window():
    """The same work in half the tick time reads twice the share, however
    long the window it fell in."""
    from chipbench import bench
    from chipbench.tests.tiny import tiny_serve
    cell = tiny_serve()
    peak = {"bf16_flops_per_s": 1e12}
    data = {"positions": list(range(100)), "sampled": 10}
    work = flops.serve_flops(cell.arch, data["positions"], 10)

    def mfu(tick_s, window_s):
        run = bench.Run(cell, 0, window_s, peak, 1, window_s=window_s,
                        data=dict(data, tick_s=tick_s))
        return bench.read_metric("mfu.serve", run)
    assert mfu(2.0, 10.0) == pytest.approx(100.0 * work / (2.0 * 1e12))
    assert mfu(1.0, 10.0) == pytest.approx(2 * mfu(2.0, 10.0))
    assert mfu(1.0, 50.0) == mfu(1.0, 10.0)
    assert mfu(0.0, 10.0) is None


def test_open_loop_is_one_schedule_with_seed_tokens():
    kw = dict(rate=4.0, seconds=75.0, shape_seed=3,
              prompt={"median": 512, "sigma": 0.9, "lo": 32, "hi": 2048},
              output={"median": 128, "sigma": 0.7, "lo": 8, "hi": 512},
              vocab=5000)
    a = TR.open_loop(2 ** 40 + 1, **kw)
    b = TR.open_loop(11, **kw)
    shape = lambda rs: [(r.due, len(r.prompt), r.max_new) for r in rs]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [r.prompt for r in a] == [r.prompt for r in
                                     TR.open_loop(2 ** 40 + 1, **kw)]
    due = np.array([r.due for r in a])
    assert len(a) == 300 and due[0] == 0 and np.all(np.diff(due) >= 0)
    assert 60.0 < due[-1] < 75.0
    lens = np.array([len(r.prompt) for r in a])
    assert lens.min() >= 32 and lens.max() <= 2048
    assert 400 < np.median(lens) < 650


def test_weights_are_the_seeds():
    a = W.make(TINY_ARCH, 2 ** 35 + 9)
    b = W.make(TINY_ARCH, 2 ** 35 + 9)
    c = W.make(TINY_ARCH, 9)
    for k in W.shapes(TINY_ARCH):
        x, y, z = (W.flatten(t)[k] for t in (a, b, c))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))


def test_reference_matches_the_program_in_float32():
    """The program's forward pass at float32 against the reference, on
    one device: the two must agree to float32 rounding."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.tp import ParallelCtx
    from repro.models.transformer import forward, lm_logits_local
    a = dict(TINY_ARCH, param_dtype="float32")
    cfg = dataclasses.replace(get_config("glm4-9b"),
                              **{k: v for k, v in a.items()})
    params = W.make(a, 5)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 24)))
    ctx = ParallelCtx()
    with jax.default_matmul_precision("highest"):
        x, _ = forward(params, tokens, cfg, ctx, remat=False)
        prog = lm_logits_local(params, x, cfg, ctx)
    ref = R.logits(params, tokens, a, "f32")
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    low = R.logits(params, tokens, a, "fp8")
    assert float(jnp.max(jnp.abs(low - ref))) > 1e-2


def test_blocked_attention_equals_whole():
    a = dict(TINY_ARCH, param_dtype="float32")
    params = W.make(a, 3)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 64))
    lp = jax.tree.map(lambda t: t[0], params["layers"])
    np.testing.assert_allclose(np.asarray(R.layer(x, lp, a, "f32", 16)),
                               np.asarray(R.layer(x, lp, a, "f32")),
                               rtol=1e-5, atol=1e-5)
