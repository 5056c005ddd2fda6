"""RoutePlan engine tests: plan construction/quantization, the PathExecutor
registry, the PlanCache, and end-to-end execute() losslessness on a mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core import routing as rt
from repro.core.collectives import (CHUNK_GRID, PATH_ORDER, PATH_ORTHO,
                                    PATH_PRIMARY, PATH_STAGED)
from repro.core.communicator import CommConfig, FlexCommunicator, bucket_for
from repro.core.topology import Collective

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 CPU devices")


def mesh2d():
    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("x", "y"))


# ---------------------------------------------------------------------------
# plan construction
# ---------------------------------------------------------------------------

def test_build_plan_quantizes_to_grain():
    plan = rt.build_plan(Collective.ALL_REDUCE, "x",
                         {"primary": 70, "staged": 20, "ortho": 10}, "y")
    units = plan.units()
    assert sum(units.values()) == CHUNK_GRID
    assert set(units) == {PATH_PRIMARY, PATH_STAGED, PATH_ORTHO}
    assert plan.paths == (PATH_PRIMARY, PATH_STAGED, PATH_ORTHO)


def test_build_plan_none_shares_is_primary_only():
    plan = rt.build_plan(Collective.ALL_GATHER, "x")
    assert plan.is_primary_only
    assert plan.units() == {PATH_PRIMARY: CHUNK_GRID}


def test_build_plan_drops_ortho_without_axis():
    plan = rt.build_plan(Collective.ALL_REDUCE, "x",
                         {"primary": 50, "staged": 25, "ortho": 25}, None)
    assert PATH_ORTHO not in plan.units()
    assert sum(plan.units().values()) == CHUNK_GRID


def test_plan_is_hashable_and_stable():
    mk = lambda: rt.build_plan(Collective.ALL_REDUCE, "x",
                               {"primary": 80, "staged": 20}, "y",
                               staged_substeps=3)
    a, b = mk(), mk()
    assert a == b and hash(a) == hash(b)
    assert len({a, b}) == 1


def test_all_to_all_folds_ortho_into_staged():
    """a2a has no ortho detour that avoids primary links: the ortho share
    must fold into the staged route at plan-build time."""
    plan = rt.build_plan(Collective.ALL_TO_ALL, "x",
                         {"primary": 50, "staged": 25, "ortho": 25}, "y")
    units = plan.units()
    assert PATH_ORTHO not in units
    ref = rt.build_plan(Collective.ALL_REDUCE, "x",
                        {"primary": 50, "staged": 25, "ortho": 25}, "y")
    folded = ref.units()
    assert units[PATH_STAGED] == (folded[PATH_STAGED] + folded[PATH_ORTHO])
    assert sum(units.values()) == CHUNK_GRID


def test_substeps_clamped():
    lo = rt.build_plan(Collective.ALL_REDUCE, "x", {"primary": 1},
                       staged_substeps=0)
    hi = rt.build_plan(Collective.ALL_REDUCE, "x", {"primary": 1},
                       staged_substeps=10_000)
    assert lo.staged_substeps == 1
    assert hi.staged_substeps == rt.MAX_STAGED_SUBSTEPS


# ---------------------------------------------------------------------------
# executor registry
# ---------------------------------------------------------------------------

def test_registry_covers_every_collective_path_cell():
    cells = {
        Collective.ALL_REDUCE: (PATH_PRIMARY, PATH_STAGED, PATH_ORTHO),
        Collective.ALL_GATHER: (PATH_PRIMARY, PATH_STAGED, PATH_ORTHO),
        Collective.REDUCE_SCATTER: (PATH_PRIMARY, PATH_STAGED, PATH_ORTHO),
        # a2a: ortho folds into staged at plan time, no ortho cell needed
        Collective.ALL_TO_ALL: (PATH_PRIMARY, PATH_STAGED),
    }
    for coll, paths in cells.items():
        for p in paths:
            assert callable(rt.executor_for(coll, p))


def test_unregistered_cell_raises():
    with pytest.raises(NotImplementedError):
        rt.executor_for(Collective.BROADCAST, PATH_STAGED)


def test_resolve_accumulate_policy():
    plan = rt.build_plan(Collective.ALL_REDUCE, "x",
                         {"primary": 50, "staged": 50})
    # sub-32-bit floats get the Pallas fp32 kernel closure
    assert rt.resolve_accumulate(plan, jnp.bfloat16) is not None
    assert rt.resolve_accumulate(plan, jnp.float16) is not None
    # f32: an fp32 accumulator is bitwise a + b — kernel is pure overhead
    assert rt.resolve_accumulate(plan, jnp.float32) is None
    # integers stay on native a + b (exact)
    assert rt.resolve_accumulate(plan, jnp.int32) is None
    # explicit override wins
    marker = lambda a, b: a
    assert rt.resolve_accumulate(plan, jnp.float32, marker) is marker
    nat = rt.build_plan(Collective.ALL_REDUCE, "x",
                        {"primary": 50, "staged": 50},
                        accumulate=rt.ACC_NATIVE)
    assert rt.resolve_accumulate(nat, jnp.float32) is None


# ---------------------------------------------------------------------------
# PlanCache
# ---------------------------------------------------------------------------

def test_plan_cache_hit_miss_retrace():
    cache = rt.PlanCache()
    build = lambda s: (lambda: rt.build_plan(Collective.ALL_REDUCE, "x", s))
    s1 = {"primary": 80, "staged": 20}
    s2 = {"primary": 50, "staged": 50}     # quantizes differently from s1
    a = cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(s1))
    assert cache.stats.misses == 1 and cache.stats.hits == 0
    b = cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(s1))
    assert b is a
    assert cache.stats.hits == 1
    # Stage-2 changed the quantized split -> same slot, new plan: a re-trace
    cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(s2))
    assert cache.stats.misses == 2 and cache.stats.retraces == 1
    # a different bucket is a fresh slot, not a re-trace
    cache.lookup(Collective.ALL_REDUCE, 2 << 20, build(s1))
    assert cache.stats.retraces == 1
    assert len(cache) == 3
    rep = cache.report()
    assert rep == {"hits": 1, "misses": 3, "retraces": 1, "size": 3}


def test_plan_cache_counts_retrace_on_return_to_previous_plan():
    """A slot oscillating A -> B -> A re-traces on EVERY flip, including
    the return to a previously-seen plan (which is a cache hit)."""
    cache = rt.PlanCache()
    build = lambda s: (lambda: rt.build_plan(Collective.ALL_REDUCE, "x", s))
    sA = {"primary": 80, "staged": 20}
    sB = {"primary": 50, "staged": 50}
    cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(sA))
    cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(sB))   # A -> B
    cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(sA))   # B -> A (hit)
    cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(sB))   # A -> B (hit)
    assert cache.stats.retraces == 3
    assert cache.stats.hits == 2 and cache.stats.misses == 2


def test_plan_cache_subquantum_share_move_is_a_hit():
    """A share move too small to change the quantized chunk_units is NOT a
    new jit variant — the cache must count a hit, not a miss/retrace."""
    cache = rt.PlanCache()
    build = lambda s: (lambda: rt.build_plan(Collective.ALL_REDUCE, "x", s))
    s1 = {"primary": 80, "staged": 20}
    s2 = {"primary": 79, "staged": 21}     # same 16-chunk split as s1
    p1 = rt.build_plan(Collective.ALL_REDUCE, "x", s1)
    p2 = rt.build_plan(Collective.ALL_REDUCE, "x", s2)
    assert p1.chunk_units == p2.chunk_units
    a = cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(s1))
    b = cache.lookup(Collective.ALL_REDUCE, 1 << 20, build(s2))
    assert b is a
    assert cache.stats.hits == 1 and cache.stats.retraces == 0


def test_communicator_plan_cache_hits_on_repeat_calls():
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"),
                            ortho_name="y")
    x = jnp.zeros((1024, 256), jnp.float32)
    p1 = comm.plan_for(Collective.ALL_REDUCE, x)
    p2 = comm.plan_for(Collective.ALL_REDUCE, x)
    assert p2 is p1
    stats = comm.plan_cache.stats
    assert stats.misses == 1 and stats.hits == 1
    rep = comm.report()["plan_cache"]
    assert rep["hits"] == 1 and rep["misses"] == 1


def test_communicator_retrace_counted_after_share_move():
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"),
                            ortho_name="y")
    # 256 MiB bucket: big enough that Stage 1 keeps secondaries active
    x = jnp.zeros((8192, 8192), jnp.float32)
    comm.plan_for(Collective.ALL_REDUCE, x)
    # force a move big enough to change the quantized split, then re-plan
    nbytes = x.size * x.dtype.itemsize
    bal = comm._balancers[(Collective.ALL_REDUCE, bucket_for(nbytes))]
    assert any(s > 0 for p, s in bal.shares.items() if p != bal.primary)
    moved_from = max((p for p in bal.shares if p != bal.primary),
                     key=lambda p: bal.shares[p])
    moved = min(20, bal.shares[moved_from])
    bal.shares[moved_from] -= moved
    bal.shares[bal.primary] += moved
    comm.plan_for(Collective.ALL_REDUCE, x)
    assert comm.plan_cache.stats.retraces == 1


def test_communicator_plan_pure_function_of_bucket():
    """Two different payload sizes in one bucket must get the SAME plan
    (same staged substeps) regardless of call order — the plan is a pure
    function of (op, bucket, shares)."""
    a = FlexCommunicator("x", 8, CommConfig(profile="h800"), ortho_name="y")
    b = FlexCommunicator("x", 8, CommConfig(profile="h800"), ortho_name="y")
    small = jnp.zeros((300, 1024), jnp.float32)      # ~1.2 MiB
    big = jnp.zeros((490, 1024), jnp.float32)        # ~1.9 MiB, same bucket
    assert bucket_for(small.size * 4) == bucket_for(big.size * 4)
    p_small_first = a.plan_for(Collective.ALL_REDUCE, small)
    p_big_after = a.plan_for(Collective.ALL_REDUCE, big)
    p_big_first = b.plan_for(Collective.ALL_REDUCE, big)
    assert p_small_first == p_big_after == p_big_first


def test_issued_log_replaced_not_doubled_by_retraces():
    """A fresh trace REPLACES the replay log: re-tracing one step between
    executed steps must not grow it, while per-step multiplicity of
    identical calls (e.g. one all_reduce per layer) is preserved."""
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"),
                            ortho_name="y")
    x = jnp.zeros((512, 512), jnp.float32)

    def trace_step():                     # 3 identical + 1 distinct call
        for _ in range(3):
            comm.plan_for(Collective.ALL_REDUCE, x)
        comm.plan_for(Collective.ALL_GATHER, x)

    trace_step()
    comm.observe_executed_step()          # promotes the trace log
    assert len(comm.issued_calls()) == 4  # multiplicity kept
    trace_step()                          # Stage-2 re-trace of the same step
    comm.observe_executed_step()
    assert len(comm.issued_calls()) == 4  # replaced, not appended
    comm.observe_executed_step()          # steps without re-trace replay it
    assert len(comm.issued_calls()) == 4


def test_nccl_backend_plans_are_primary_only_and_cached():
    comm = FlexCommunicator("x", 8, CommConfig(backend="nccl",
                                               profile="h800"))
    x = jnp.zeros((64, 64), jnp.float32)
    p = comm.plan_for(Collective.ALL_GATHER, x)
    assert p.is_primary_only
    comm.plan_for(Collective.ALL_GATHER, x)
    assert comm.plan_cache.stats.hits == 1


def test_staged_substeps_scale_with_payload():
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    shares = {"primary": 60, "staged": 40}
    small = comm.staged_substeps_for(Collective.ALL_REDUCE, 1 << 20, shares)
    big = comm.staged_substeps_for(Collective.ALL_REDUCE, 1 << 30, shares)
    assert 1 <= small <= big <= rt.MAX_STAGED_SUBSTEPS
    assert big >= rt.DEFAULT_STAGED_SUBSTEPS
    none = comm.staged_substeps_for(Collective.ALL_REDUCE, 1 << 30,
                                    {"primary": 100})
    assert none == 1


# ---------------------------------------------------------------------------
# execute() end-to-end on a mesh
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("collective,ref", [
    (Collective.ALL_REDUCE, lambda v: lax.psum(v, "x")),
    (Collective.ALL_GATHER, lambda v: lax.all_gather(v, "x")),
])
def test_execute_matches_reference_payload_layout(collective, ref):
    mesh = mesh2d()
    plan = rt.build_plan(collective, "x",
                         {"primary": 50, "staged": 30, "ortho": 20}, "y",
                         staged_substeps=3)
    x = jnp.arange(4 * 6 * 5, dtype=jnp.float32).reshape(4 * 6, 5) * 0.37
    f = shard_map(lambda v: rt.execute(plan, v), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P() if
                  collective is Collective.ALL_GATHER else P("x"),
                  check_vma=False)
    r = shard_map(ref, mesh=mesh, in_specs=(P("x"),),
                  out_specs=P() if collective is Collective.ALL_GATHER
                  else P("x"), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=2e-6)


@needs8
def test_execute_matches_reference_columns_layout():
    mesh = mesh2d()
    plan = rt.build_plan(Collective.REDUCE_SCATTER, "x",
                         {"primary": 50, "staged": 30, "ortho": 20}, "y",
                         staged_substeps=2)
    x = jnp.arange(4 * 8 * 3, dtype=jnp.float32).reshape(4 * 8, 3) * 0.25
    f = shard_map(lambda v: rt.execute(plan, v), mesh=mesh, in_specs=(P(),),
                  out_specs=P("x"), check_vma=False)
    r = shard_map(lambda v: lax.psum_scatter(v, "x", scatter_dimension=0,
                                             tiled=True),
                  mesh=mesh, in_specs=(P(),), out_specs=P("x"),
                  check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=1e-6)


@needs8
def test_execute_all_to_all_with_folded_ortho():
    mesh = mesh2d()
    x = jnp.arange(4 * 8 * 5, dtype=jnp.float32).reshape(4 * 8, 5)
    got = shard_map(
        lambda v: rt.flex_all_to_all(v, "x", shares={"primary": 40,
                                                     "staged": 30,
                                                     "ortho": 30},
                                     ortho_name="y"),
        mesh=mesh, in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    ref = shard_map(lambda v: lax.all_to_all(v, "x", 0, 0, tiled=True),
                    mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                    check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(got)(x)),
                                  np.asarray(jax.jit(ref)(x)))


@needs8
def test_pipelined_staged_ring_bit_exact_any_substeps():
    """Pure data movement: the chunk-pipelined all-gather ring is
    bit-identical for every pipeline depth."""
    from repro.core.collectives import ring_all_gather
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("x",))
    x = jnp.arange(8 * 13, dtype=jnp.float32) * 0.31
    outs = []
    for s in (1, 2, 3, 8):
        f = shard_map(lambda v, s=s: ring_all_gather(v, "x", substeps=s),
                      mesh=mesh, in_specs=(P("x"),), out_specs=P(),
                      check_vma=False)
        outs.append(np.asarray(jax.jit(f)(x)))
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ---------------------------------------------------------------------------
# review regressions
# ---------------------------------------------------------------------------

def test_resolve_accumulate_never_downcasts_wide_dtypes():
    """ACC_AUTO must not round float64/complex payloads through a float32
    accumulator (lossless contract)."""
    plan = rt.build_plan(Collective.ALL_REDUCE, "x",
                         {"primary": 50, "staged": 50})
    assert rt.resolve_accumulate(plan, jnp.float64) is None
    assert rt.resolve_accumulate(plan, jnp.complex64) is None
    assert rt.resolve_accumulate(plan, jnp.float16) is not None


def test_resolve_accumulate_explicit_kernel_policy():
    """ACC_KERNEL_FP32 is an explicit opt-in: forced for real floats (even
    f64 — the caller accepts fp32 rounding), rejected for dtypes the
    kernel cannot represent."""
    plan = rt.build_plan(Collective.ALL_REDUCE, "x",
                         {"primary": 50, "staged": 50},
                         accumulate=rt.ACC_KERNEL_FP32)
    assert rt.resolve_accumulate(plan, jnp.float64) is not None
    assert rt.resolve_accumulate(plan, jnp.float32) is not None
    with pytest.raises(TypeError):
        rt.resolve_accumulate(plan, jnp.int32)
    with pytest.raises(TypeError):
        rt.resolve_accumulate(plan, jnp.complex64)


def test_nccl_mode_does_not_grow_replay_log():
    comm = FlexCommunicator("x", 8, CommConfig(backend="nccl",
                                               profile="h800"))
    x = jnp.zeros((64, 64), jnp.float32)
    for _ in range(5):
        comm.plan_for(Collective.ALL_REDUCE, x)
    assert comm.issued_calls() == []


@needs8
def test_execute_rejects_indivisible_leading_dim():
    """Multi-path reduce_scatter must fail loudly (not return garbage) when
    the leading dim does not divide the axis size."""
    mesh = mesh2d()
    plan = rt.build_plan(Collective.REDUCE_SCATTER, "x",
                         {"primary": 50, "staged": 50})
    x = jnp.arange(6 * 2, dtype=jnp.float32).reshape(6, 2)
    f = shard_map(lambda v: rt.execute(plan, v), mesh=mesh, in_specs=(P(),),
                  out_specs=P("x"), check_vma=False)
    with pytest.raises(Exception):
        jax.jit(f)(x)


def test_config_tag_isolates_registry_entries():
    """Trace-only tooling (dry-run) must not share a communicator — and
    therefore a Stage-2 replay log — with a live workload."""
    from repro.core.communicator import comm_destroy_all, comm_init_rank
    comm_destroy_all()
    live = comm_init_rank("x", 8, CommConfig(profile="h800"))
    probe = comm_init_rank("x", 8, CommConfig(profile="h800", tag="dryrun"))
    assert live is not probe
    probe.plan_for(Collective.ALL_REDUCE, jnp.zeros((512, 512), jnp.float32))
    assert live.issued_calls() == []
    comm_destroy_all()


def test_ctx_reset_issued_clears_all_comms():
    from repro.core.communicator import comm_destroy_all
    from repro.models.tp import ParallelCtx
    comm_destroy_all()
    ctx = ParallelCtx(tp_axis="x", dp_axis="y", tp_size=4, dp_size=2,
                      comm_config=CommConfig(profile="h800"))
    x = jnp.zeros((512, 512), jnp.float32)
    for comm in ctx.comms():
        comm.plan_for(Collective.ALL_REDUCE, x)
        assert comm.issued_calls()
    ctx.reset_issued()
    assert all(not c.issued_calls() for c in ctx.comms())
    comm_destroy_all()
