"""Compile the main-path kernels for a described TPU v5e, without a chip.

Each case lowers with ``interpret=False`` against ``v5e:2x2`` devices and
checks that the compiled program holds the Pallas kernel
(``tpu_custom_call``): the compiler refuses here what it would refuse on
the chip (tile alignment, VMEM budget).  Nothing runs, so these say
nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core.routing import flex_all_reduce
from repro.kernels import chunk_accumulate as ca
from repro.kernels import codec
from repro.kernels import flash_decode as fd
from repro.kernels import ops

MiB = 2 ** 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  (no TPU compiler here)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _rows(nbytes, dtype):
    return nbytes // jnp.dtype(dtype).itemsize // ca.LANE


@pytest.mark.parametrize("kernel", ["chunk_accumulate_2d", "fp8_encode_2d",
                                    "bf16_pack_2d", "paged_flash_decode"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if kernel == "chunk_accumulate_2d":
        # the staged ring's reduce step: bf16 payload, f32 accumulate
        a = sds((_rows(16 * MiB, jnp.bfloat16), ca.LANE), jnp.bfloat16)
        text = _compiled_text(
            lambda x, y: ca.chunk_accumulate_2d(x, y, acc_dtype=jnp.float32,
                                                interpret=False), a, a)
    elif kernel == "fp8_encode_2d":
        x = sds((_rows(4 * MiB, jnp.float32), ca.LANE), jnp.float32)
        text = _compiled_text(
            lambda v: codec.fp8_encode_2d(v, fmt="fp8_e4m3",
                                          interpret=False), x)
    elif kernel == "bf16_pack_2d":
        x = sds((_rows(4 * MiB, jnp.float32), ca.LANE), jnp.float32)
        text = _compiled_text(
            lambda v: codec.bf16_pack_2d(v, interpret=False), x)
    else:
        # glm4-9b attention heads over a 512-block bf16 pool
        rows, hq, hkv, hd, bs, maxb = 32, 32, 2, 128, 16, 32
        q = sds((rows, hq, hd), jnp.bfloat16)
        pool = sds((512, bs, hkv, hd), jnp.bfloat16)
        tables = sds((rows, maxb), jnp.int32)
        kv_valid = sds((rows,), jnp.int32)
        text = _compiled_text(
            lambda *a: fd.paged_flash_decode_pool(*a, interpret=False),
            q, pool, pool, tables, kv_valid)
    assert "tpu_custom_call" in text


def test_paged_serve_step_fits_one_v5e_chip(one_chip):
    """The packed decode step ``chip_smoke.py`` serves: glm4-9b at its
    published widths, 16 of 40 layers, the launcher's pool and top
    batch bucket.  9.0 GB of bf16 weights must leave the step inside one
    chip's 16 GB."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import init_params, single_device_ctx
    from repro.serving.engine import PagedServeConfig, PagedServeEngine

    cfg = dataclasses.replace(get_config("glm4-9b"), n_layers=16)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    scfg = PagedServeConfig(max_requests=8, cache_len=96, kv_block=16,
                            max_tokens_in_flight=32)
    engine = PagedServeEngine(params, cfg, single_device_ctx(), scfg)
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    rows, reqs = scfg.max_tokens_in_flight, scfg.max_requests
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    compiled = engine._step_builder().lower(
        on_chip(params), on_chip(engine.pool), i32(rows), i32(rows),
        i32(rows), i32(*engine.kv.tables.shape), i32(reqs)).compile()
    engine.close()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 9e9 < used < 16e9


def test_kimi_latent_step_updates_its_pool_in_place_on_one_v5e_chip(
        one_chip):
    """The benchmark's Kimi K2 step: published widths, 1 dense + 8 expert
    layers, 8 of 384 experts held, vocabulary 20480, at its top bucket of
    256 rows over the latent pool of 16 requests of 12288 positions
    (2.27 GB).  It fits one chip's 16 GB, and the donated pool is written
    in place: aliased to the output, never copied whole."""
    import dataclasses
    import functools
    import re

    from repro.configs import get_config
    from repro.models import init_params, single_device_ctx
    from repro.models.transformer import init_paged_pool
    from repro.serving.engine import PagedServeConfig, paged_step_builder

    base = get_config("kimi-k2-1t-a32b")
    cfg = dataclasses.replace(base, n_layers=9, vocab=20480,
                              moe=dataclasses.replace(base.moe, held=(0, 8)))
    ctx = single_device_ctx()
    scfg = PagedServeConfig(max_requests=16, cache_len=12288, kv_block=16,
                            max_tokens_in_flight=256, min_bucket=8)
    pcfg = scfg.paged()
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    pool = jax.eval_shape(functools.partial(init_paged_pool, cfg, ctx, pcfg))
    rows, reqs = scfg.max_tokens_in_flight, scfg.max_requests
    compiled = paged_step_builder(cfg, ctx, pcfg).lower(
        on_chip(params), on_chip(pool), i32(rows), i32(rows), i32(rows),
        i32(reqs, pcfg.max_blocks_per_req), i32(reqs)).compile()
    mem = compiled.memory_analysis()
    latent = pool["latent"]
    assert latent.shape == (9, 12288, 16, 640)
    assert mem.alias_size_in_bytes >= latent.size * 2
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 11e9 < used < 16e9
    whole = re.escape("bf16[9,12288,16,640]") + r"\{[^}]*\} copy\("
    assert not re.search(whole, compiled.as_text())


def test_dense_paged_step_updates_its_kv_pool_in_place_on_v5e(one_chip):
    """The dense step of the deepseek-67b chat cell at published widths
    and with its pool (32 requests of 2560 positions: 5120 blocks, 168 MB
    a layer, more than the chip's fast memory holds), cut to 2 layers and
    16 rows: the loop carries the stacked K and V pools and the step
    donates them, so both are aliased to the output, no op's result is
    one layer's pool sliced out of the stack, and no dynamic-update-slice
    or copy writes the whole stack."""
    import dataclasses
    import functools
    import re

    from repro.configs import get_config
    from repro.models import init_params, single_device_ctx
    from repro.models.transformer import init_paged_pool
    from repro.serving.engine import PagedServeConfig, paged_step_builder

    cfg = dataclasses.replace(get_config("deepseek-67b"), n_layers=2)
    ctx = single_device_ctx()
    scfg = PagedServeConfig(max_requests=32, cache_len=2560, kv_block=16,
                            max_tokens_in_flight=16, min_bucket=16)
    pcfg = scfg.paged()
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    pool = jax.eval_shape(functools.partial(init_paged_pool, cfg, ctx, pcfg))
    rows, reqs = scfg.max_tokens_in_flight, scfg.max_requests
    compiled = paged_step_builder(cfg, ctx, pcfg).lower(
        on_chip(params), on_chip(pool), i32(rows), i32(rows), i32(rows),
        i32(reqs, pcfg.max_blocks_per_req), i32(reqs)).compile()
    k = pool["k"]
    assert k.shape == (2, 5120, 16, 8, 128) and k.dtype == jnp.bfloat16
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * k.size * 2
    text = compiled.as_text()
    assert "bf16[1,5120,16,8,128]" not in text         # one layer's pool
    whole = re.compile(r"^\s*(?:ROOT )?%?(\S+) = bf16\[2,5120,16,8,128\]"
                       r"\S* ([\w-]+)\(", re.M)
    writes = whole.findall(text)
    assert writes                      # the parameters and the scatter
    for name, op in writes:
        assert op not in ("copy", "dynamic-update-slice"), (name, op)
        assert "dynamic-update-slice" not in name, (name, op)


def test_flex_all_reduce_compiles_staged_kernel_for_v5e(topo, monkeypatch):
    # a trace interpreted earlier in this process must not be reused
    jax.clear_caches()
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    shares = {"primary": 70, "staged": 30}
    n = 16 * MiB // 2

    def ar(x):
        return flex_all_reduce(x, "data", shares=shares, ortho_name="model")

    x = jax.ShapeDtypeStruct((2 * n,), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    f = shard_map(ar, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
                  check_vma=False)
    text = jax.jit(f).lower(x).compile().as_text()
    assert "collective-permute" in text      # the staged ring's hops
    assert "tpu_custom_call" in text         # its chunk_accumulate kernel
    jax.clear_caches()
