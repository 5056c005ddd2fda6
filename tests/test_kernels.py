"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.chunk_accumulate import LANE, SUBLANE, chunk_accumulate_2d
from repro.kernels.payload_partition import BLOCK, extract_segment, \
    merge_segments


# ---------------------------------------------------------------------------
# chunk_accumulate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (264, 128),
                                   (1024, 384)])
def test_chunk_accumulate_2d_matches_ref(dtype, shape):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    if dtype == jnp.int32:
        a = jax.random.randint(k1, shape, -100, 100, dtype=jnp.int32)
        b = jax.random.randint(k2, shape, -100, 100, dtype=jnp.int32)
    else:
        a = jax.random.normal(k1, shape, dtype=jnp.float32).astype(dtype)
        b = jax.random.normal(k2, shape, dtype=jnp.float32).astype(dtype)
    got = chunk_accumulate_2d(a, b, interpret=True)
    want = ref.chunk_accumulate_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64))


def test_accumulate_fp32_path_beats_bf16_accumulation():
    """The acc_dtype=fp32 design point: adding a tiny value to a large one
    in bf16 loses it; the kernel's fp32 accumulate keeps it (then rounds
    once on store)."""
    a = jnp.full((8, 128), 256.0, dtype=jnp.bfloat16)
    b = jnp.full((8, 128), 1.0, dtype=jnp.bfloat16)
    got = chunk_accumulate_2d(a, b, acc_dtype=jnp.float32, interpret=True)
    # 257 rounds to 256 in bf16 either way, but with acc fp32 the rounding
    # happens once; check exact agreement with the oracle.
    want = ref.chunk_accumulate_ref(a, b, acc_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@given(n=st.integers(1, 5000),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
@settings(max_examples=25, deadline=None)
def test_property_accumulate_arbitrary_shapes(n, dtype):
    """ops.accumulate pads any payload to tiles and matches a + b."""
    a = (jnp.arange(n, dtype=jnp.float32) * 0.37).astype(dtype)
    b = (jnp.arange(n, dtype=jnp.float32) * -0.11).astype(dtype)
    got = ops.accumulate(a, b)
    want = ref.chunk_accumulate_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64))


def test_accumulate_is_ring_pluggable():
    """The ops.ring_accumulate_fn closure drops into ring_all_reduce."""
    import jax
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.core.collectives import ring_all_reduce
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("x",))
    x = jnp.arange(8 * 16, dtype=jnp.float32) * 0.25

    def ring(xs):
        return ring_all_reduce(xs, "x", accumulate=ops.ring_accumulate_fn())

    f = shard_map(ring, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                  check_vma=False)
    r = shard_map(lambda xs: lax.psum(xs, "x"), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=1e-6)


# ---------------------------------------------------------------------------
# payload split / merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_blocks,start", [(1, 0), (2, 1), (3, 5)])
def test_extract_segment_matches_ref(dtype, n_blocks, start):
    total_blocks = 8
    x = (jnp.arange(total_blocks * BLOCK, dtype=jnp.float32) * 0.5).astype(dtype)
    got = extract_segment(x, start, n_blocks, interpret=True)
    want = ref.extract_segment_ref(x, start, n_blocks, block=BLOCK)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@given(sizes=st.lists(st.integers(1, 4), min_size=1, max_size=4))
@settings(max_examples=10, deadline=None)
def test_property_split_merge_roundtrip(sizes):
    """extract_segment per route + merge_segments == identity."""
    total = sum(sizes)
    x = jnp.arange(total * BLOCK, dtype=jnp.float32)
    segs, off = [], 0
    for s in sizes:
        segs.append(extract_segment(x, off, s, interpret=True))
        off += s
    back = merge_segments(segs, block=BLOCK)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    want = ref.merge_segments_ref(segs)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(want))
