"""Losslessness of the multi-path collectives (the paper's headline claim).

Every FlexLink collective, under any share split across the primary /
staged / ortho routes, is validated against the single-path ``jax.lax``
reference on a real multi-device mesh: *bit-exact* for pure data movement
(all_gather / all_to_all — no compression anywhere, the paper's lossless
claim) and exact-up-to-summation-order for reductions (a ring reduce
associates differently than psum's tree — NCCL's own algorithms differ the
same way; integer reductions stay bit-exact).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.core import collectives as mp

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 CPU devices")


def mesh2d():
    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("x", "y"))


def run_sharded(fn, x, mesh, spec=P("x")):
    f = shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                  check_vma=False)
    return jax.jit(f)(x)


SHARE_CASES = [
    {"primary": 100},
    {"primary": 80, "staged": 20},
    {"primary": 70, "staged": 20, "ortho": 10},
    {"primary": 0, "staged": 100},
    {"primary": 34, "staged": 33, "ortho": 33},
]


@pytest.mark.parametrize("shares", SHARE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_flex_all_reduce_exact(shares, dtype):
    mesh = mesh2d()
    if dtype == jnp.int32:
        x = jnp.arange(4 * 6 * 5).reshape(4 * 6, 5).astype(dtype)
    else:
        x = (jnp.arange(4 * 6 * 5, dtype=jnp.float32)
             .reshape(4 * 6, 5) * 0.37).astype(dtype)

    def flex(xs):
        return mp.flex_all_reduce(xs, "x", shares=shares, ortho_name="y")

    def ref(xs):
        return lax.psum(xs, "x")

    got = np.asarray(run_sharded(flex, x, mesh))
    want = np.asarray(run_sharded(ref, x, mesh))
    if dtype == jnp.int32:
        np.testing.assert_array_equal(got, want)
    else:
        rtol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
        np.testing.assert_allclose(got.astype(np.float64),
                                   want.astype(np.float64), rtol=rtol)


@pytest.mark.parametrize("shares", SHARE_CASES)
def test_flex_all_gather_exact(shares):
    mesh = mesh2d()
    x = jnp.arange(4 * 3 * 7, dtype=jnp.float32).reshape(4 * 3, 7) * 1.5

    def flex(xs):
        return mp.flex_all_gather(xs, "x", shares=shares, ortho_name="y",
                                  tiled=True)

    def ref(xs):
        return lax.all_gather(xs, "x", tiled=True)

    f = shard_map(flex, mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                  check_vma=False)
    r = shard_map(ref, mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


@pytest.mark.parametrize("shares", SHARE_CASES)
def test_flex_reduce_scatter_exact(shares):
    mesh = mesh2d()
    x = jnp.arange(4 * 8 * 3, dtype=jnp.float32).reshape(4 * 8, 3) * 0.25

    def flex(xs):
        return mp.flex_reduce_scatter(xs, "x", shares=shares, ortho_name="y")

    def ref(xs):
        return lax.psum_scatter(xs, "x", scatter_dimension=0, tiled=True)

    f = shard_map(flex, mesh=mesh, in_specs=(P(),), out_specs=P("x"),
                  check_vma=False)
    r = shard_map(ref, mesh=mesh, in_specs=(P(),), out_specs=P("x"),
                  check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=1e-6)


@pytest.mark.parametrize("shares", SHARE_CASES)
def test_flex_all_to_all_exact(shares):
    mesh = mesh2d()
    x = jnp.arange(4 * 8 * 5, dtype=jnp.float32).reshape(4 * 8, 5)

    def flex(xs):
        return mp.flex_all_to_all(xs, "x", split_axis=0, concat_axis=0,
                                  shares=shares, ortho_name="y")

    def ref(xs):
        return lax.all_to_all(xs, "x", 0, 0, tiled=True)

    got = run_sharded(flex, x, mesh)
    want = run_sharded(ref, x, mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ring_all_gather_matches_native():
    mesh = mesh2d()
    x = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4 * 2, 3)

    def ring(xs):
        return mp.ring_all_gather(xs, "x")

    def native(xs):
        return lax.all_gather(xs, "x")

    f = shard_map(ring, mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                  check_vma=False)
    r = shard_map(native, mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


def test_ring_all_reduce_matches_psum():
    mesh = mesh2d()
    x = jnp.arange(4 * 5, dtype=jnp.float32).reshape(4 * 5) * 0.5

    def ring(xs):
        return mp.ring_all_reduce(xs, "x")

    got = run_sharded(ring, x, mesh)
    want = run_sharded(lambda xs: lax.psum(xs, "x"), x, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@given(units=st.tuples(st.integers(0, 100), st.integers(0, 100),
                       st.integers(0, 100)).filter(lambda u: sum(u) > 0),
       n_elem=st.integers(1, 97))
@settings(max_examples=25, deadline=None)
def test_property_partition_merge_roundtrip(units, n_elem):
    x = jnp.arange(n_elem, dtype=jnp.float32) * 0.123
    shares = dict(zip(mp.PATH_ORDER, units))
    plan = mp.quantize_shares(shares, mp.PATH_ORDER)
    plan = {k: v for k, v in plan.items() if v > 0}
    segs, pad = mp.partition_payload(x, plan, mp.PATH_ORDER)
    back = mp.merge_payload(segs, mp.PATH_ORDER, pad, x.shape, x.dtype)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@given(units=st.tuples(st.integers(0, 50), st.integers(0, 50),
                       st.integers(0, 50)).filter(lambda u: sum(u) > 0))
@settings(max_examples=50, deadline=None)
def test_property_quantize_preserves_total(units):
    shares = dict(zip(mp.PATH_ORDER, units))
    q = mp.quantize_shares(shares, mp.PATH_ORDER)
    assert sum(q.values()) == mp.CHUNK_GRID
    assert all(v >= 0 for v in q.values())
    # zero-share paths stay zero
    for p, u in shares.items():
        if u == 0:
            assert q[p] == 0


@pytest.mark.parametrize("shares", [{"primary": 60, "staged": 20,
                                     "ortho": 20},
                                    {"primary": 0, "ortho": 100}])
def test_flex_all_reduce_exact_with_ortho_sharded_payload(shares):
    """REGRESSION (found via seq-sharded decode): the ortho detour must be
    lossless even when the payload DIFFERS across the ortho axis (data-
    sharded activations) — the original re-shard-and-gather implementation
    silently mixed rows."""
    mesh = mesh2d()
    x = jnp.arange(4 * 2 * 6, dtype=jnp.float32).reshape(4 * 2, 6) * 0.5

    def flex(xs):
        return mp.flex_all_reduce(xs, "x", shares=shares, ortho_name="y")

    def ref(xs):
        return lax.psum(xs, "x")

    # payload sharded over BOTH axes -> differs across the ortho axis
    f = shard_map(flex, mesh=mesh, in_specs=(P("x", "y"),),
                  out_specs=P("x", "y"), check_vma=False)
    r = shard_map(ref, mesh=mesh, in_specs=(P("x", "y"),),
                  out_specs=P("x", "y"), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=1e-6)


@pytest.mark.parametrize("shares", [{"primary": 70, "staged": 15,
                                     "ortho": 15}])
def test_flex_all_gather_exact_with_ortho_sharded_payload(shares):
    mesh = mesh2d()
    x = jnp.arange(4 * 3 * 4, dtype=jnp.float32).reshape(4 * 3, 4)

    def flex(xs):
        return mp.flex_all_gather(xs, "x", shares=shares, ortho_name="y",
                                  tiled=True)

    def ref(xs):
        return lax.all_gather(xs, "x", tiled=True)

    f = shard_map(flex, mesh=mesh, in_specs=(P("x", "y"),),
                  out_specs=P(None, "y"), check_vma=False)
    r = shard_map(ref, mesh=mesh, in_specs=(P("x", "y"),),
                  out_specs=P(None, "y"), check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


@given(pu=st.integers(0, 100), su=st.integers(0, 100),
       ou=st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_property_flex_all_reduce_any_shares(pu, su, ou):
    """Any share vector (hypothesis-driven) keeps the all-reduce lossless."""
    if pu + su + ou == 0:
        pu = 1
    mesh = mesh2d()
    x = jnp.arange(4 * 4 * 4, dtype=jnp.float32).reshape(4 * 4, 4) * 0.5
    shares = {"primary": pu, "staged": su, "ortho": ou}

    f = shard_map(lambda v: mp.flex_all_reduce(v, "x", shares=shares,
                                               ortho_name="y"),
                  mesh=mesh, in_specs=(P("x", "y"),),
                  out_specs=P("x", "y"), check_vma=False)
    r = shard_map(lambda v: lax.psum(v, "x"), mesh=mesh,
                  in_specs=(P("x", "y"),), out_specs=P("x", "y"),
                  check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=1e-6)


def test_tree_all_reduce_matches_psum():
    """Recursive-doubling all-reduce (paper §6 future work) is exact."""
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("x",))
    x = jnp.arange(8 * 6, dtype=jnp.float32).reshape(8 * 6) * 0.25

    f = shard_map(lambda v: mp.tree_all_reduce(v, "x"), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    r = shard_map(lambda v: lax.psum(v, "x"), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=1e-6)


@pytest.mark.parametrize("substeps", [1, 2, 4])
def test_staged_ring_bf16_fp32_kernel_bit_exact_vs_psum(substeps):
    """The chunk-pipelined staged ring with the Pallas fp32-accumulate
    kernel matches lax.psum BIT-EXACTLY for bf16 payloads whose sums are
    representable: the kernel accumulates in fp32 (one rounding per step on
    exact values), so no low bits are lost across the N-1 ring steps."""
    from repro.kernels import ops as kops
    mesh = mesh2d()
    # integer-valued bf16: all partial sums over 4 ranks stay exact
    x = jnp.arange(4 * 6 * 8, dtype=jnp.float32).reshape(4 * 6, 8)
    x = (x % 61.0).astype(jnp.bfloat16)

    def flex(xs):
        return mp.flex_all_reduce(xs, "x", shares={"primary": 0,
                                                   "staged": 100},
                                  ortho_name="y",
                                  accumulate=kops.ring_accumulate_fn(),
                                  substeps=substeps)

    f = shard_map(flex, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                  check_vma=False)
    r = shard_map(lambda xs: lax.psum(xs, "x"), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    got = np.asarray(jax.jit(f)(x).astype(jnp.float32))
    want = np.asarray(jax.jit(r)(x).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)


def test_staged_ring_default_accumulate_is_kernel_bf16_exact():
    """Without an explicit accumulate, the routing layer injects the Pallas
    fp32 kernel on the staged path for floating payloads (the plan's
    ACC_AUTO policy) — same bit-exact result as passing it by hand."""
    mesh = mesh2d()
    x = jnp.arange(4 * 5 * 4, dtype=jnp.float32).reshape(4 * 5, 4)
    x = (x % 29.0).astype(jnp.bfloat16)

    f = shard_map(lambda xs: mp.flex_all_reduce(
                      xs, "x", shares={"primary": 0, "staged": 100},
                      ortho_name="y"),
                  mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                  check_vma=False)
    r = shard_map(lambda xs: lax.psum(xs, "x"), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(f)(x).astype(jnp.float32)),
        np.asarray(jax.jit(r)(x).astype(jnp.float32)))
