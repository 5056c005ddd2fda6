"""Overlapped bucketed gradient sync (DESIGN.md §11): GradBucketer
packing, bucketed-vs-monolithic bit-exactness, issue/await windows with
disjoint per-bucket Stage-2 multisets, the contention pricing model's
serial-case parity, and the overlap-aware roofline bounds.

Bit-exactness discipline (same as tests/test_cluster.py): reductions
associate differently per schedule, so parity tests drive them with
SMALL-INTEGER payloads — every partial sum is exactly representable in
fp32 AND bf16, making any summation order produce identical bits.
"""

from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.communicator import (CommConfig, FlexCommunicator,
                                     comm_destroy_all, comm_init_rank)
from repro.core.links import PROFILES
from repro.core.simulator import PathTimingModel
from repro.core.topology import Collective
from repro.models.tp import ParallelCtx
from repro.roofline.analytic import step_time_bounds
from repro.runtime.program import StepProgram
from repro.train.bucketer import GradBucketer
from repro.train.train_step import sync_grads

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 CPU devices")

AR = Collective.ALL_REDUCE


@pytest.fixture(autouse=True)
def _fresh_comms():
    comm_destroy_all()
    yield
    comm_destroy_all()


def _mb(nbytes: int) -> float:
    return nbytes / 2.0 ** 20


# ---------------------------------------------------------------------------
# GradBucketer packing rules (pure metadata — no mesh needed)
# ---------------------------------------------------------------------------

def test_bucketer_splits_big_leaves_and_respects_target():
    grads = {"big": jnp.zeros((16, 32), jnp.float32),   # 2048 B, 128 B/row
             "small": jnp.zeros((4,), jnp.float32)}     # 16 B
    b = GradBucketer(grads, bucket_mb=_mb(512))
    total = sum(bk.nbytes for bk in b.buckets)
    assert total == 16 * 32 * 4 + 4 * 4
    # big splits into 4-row slabs; every bucket holds <= target unless a
    # single piece overflows (none does here)
    assert all(bk.nbytes <= 512 for bk in b.buckets)
    assert [bk.tag for bk in b.buckets] == \
        [f"g{i}" for i in range(len(b.buckets))]
    # reverse leaf order: the LAST leaf ("small") leads the issue order
    first = b.buckets[0].pieces[0]
    leaves = jax.tree_util.tree_leaves(grads)
    assert leaves[first.leaf].shape == (4,)
    # slabs of the split leaf are issued end-of-stack first
    slabs = [p.rows for bk in b.buckets for p in bk.pieces
             if p.rows is not None]
    assert slabs == sorted(slabs, reverse=True)


def test_bucketer_dtype_and_expert_homogeneity():
    grads = {"a": jnp.zeros((8, 8), jnp.float32),
             "moe": {"experts": {"w": jnp.zeros((8, 8), jnp.float32)}},
             "z": jnp.zeros((8, 8), jnp.bfloat16)}
    b = GradBucketer(grads, bucket_mb=1.0, ep=True)   # target >> leaves
    # three buckets despite the huge target: bf16 / expert / dense f32
    assert len(b.buckets) == 3
    kinds = {(bk.dtype, bk.expert) for bk in b.buckets}
    assert kinds == {("bfloat16", False), ("float32", True),
                     ("float32", False)}
    # without ep, experts merge with the dense f32 bucket
    b2 = GradBucketer(grads, bucket_mb=1.0, ep=False)
    assert len(b2.buckets) == 2


def test_bucketer_rejects_zero_and_roundtrips_without_comms():
    grads = {"w": jnp.arange(64, dtype=jnp.float32).reshape(16, 4),
             "b": jnp.arange(5, dtype=jnp.float32)}
    with pytest.raises(ValueError):
        GradBucketer(grads, bucket_mb=0.0)
    # no live communicators: every reduce no-ops, so sync must be the
    # slice/concat identity — bit-exact passthrough
    ctx = ParallelCtx()
    out = GradBucketer(grads, bucket_mb=_mb(64)).sync(grads, ctx)
    jax.tree.map(np.testing.assert_array_equal, out, grads)


# ---------------------------------------------------------------------------
# parity property test: bucketed == monolithic, bit-exact
# {fp32, bf16} x {1, 2}-node x ep_a2a on/off
# ---------------------------------------------------------------------------

def _parity_ctx(layout: str):
    if layout == "flat":
        mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
        ctx = ParallelCtx(dp_axis="data", dp_size=4,
                          comm_config=CommConfig(profile="tpu_v5e",
                                                 tag="ov-flat"))
        return mesh, ctx, P("data"), 4
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("node", "data"))
    ctx = ParallelCtx(dp_axis="data", node_axis="node",
                      dp_size=4, node_size=2,
                      comm_config=CommConfig(profile="tpu_v5e",
                                             tag="ov-node"))
    return mesh, ctx, P(("node", "data")), 8


def _int_grads(rng, world: int, ep: bool, dtype):
    g = {
        # big enough to split at the test's bucket target
        "deep": {"w": rng.integers(0, 8, size=(world * 24, 8))},
        "mid": rng.integers(0, 8, size=(world * 4, 3)),
        "tail": rng.integers(0, 8, size=(world, 2)),
    }
    if ep:
        g["moe"] = {"experts": {"wi": rng.integers(0, 8,
                                                   size=(world * 8, 5))}}
    return jax.tree.map(
        lambda a: jnp.asarray(a.astype(np.float32)).astype(dtype), g)


def _check_sync_parity(layout, dtype, ep, seed):
    comm_destroy_all()
    mesh, ctx, spec, world = _parity_ctx(layout)
    cfg = SimpleNamespace(moe=SimpleNamespace(impl="ep_a2a") if ep else None)
    grads = _int_grads(np.random.default_rng(seed), world, ep, dtype)

    def run(fn):
        f = shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                      check_vma=False)
        return jax.tree.map(np.asarray,
                            jax.tree.map(lambda a: a.astype(jnp.float32),
                                         jax.jit(f)(grads)))

    mono = run(lambda t: sync_grads(t, cfg, ctx))
    buck = run(lambda t: ctx.await_all(
        sync_grads(t, cfg, ctx, bucket_mb=_mb(256))))
    jax.tree.map(np.testing.assert_array_equal, buck, mono)


@needs8
@settings(max_examples=10, deadline=None)
@given(layout=st.sampled_from(["flat", "cluster"]),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       ep=st.booleans(),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_bucketed_sync_bit_exact_vs_monolithic(layout, dtype, ep, seed):
    _check_sync_parity(layout, dtype, ep, seed)


@needs8
@pytest.mark.parametrize("layout,dtype,ep", [
    ("flat", "float32", False),
    ("flat", "bfloat16", True),
    ("cluster", "float32", True),
    ("cluster", "bfloat16", False),
])
def test_bucketed_sync_parity_fixed_grid(layout, dtype, ep):
    """Hypothesis-free anchor over the corners of the property grid, so
    the parity contract is enforced even where hypothesis is absent."""
    _check_sync_parity(layout, dtype, ep, seed=7)


# ---------------------------------------------------------------------------
# issue windows: disjoint per-bucket Stage-2 multisets + contention factor
# ---------------------------------------------------------------------------

def test_inflight_buckets_keep_disjoint_stage2_multisets():
    comm = comm_init_rank("x", 8, CommConfig(profile="h800"))
    comm.register_recorder("train")
    with comm.recording(comm.recorder("train"), name="train"):
        with comm.issue_scope("g0"):
            comm.plan_for(AR, jnp.zeros((512, 512), jnp.float32))
        with comm.issue_scope("g1"):
            comm.plan_for(AR, jnp.zeros((256, 256), jnp.float32))
    # base + two sub-recorders, each with exactly its own bucket's call
    assert len(comm.family_recorders("train")) == 3
    c0 = comm.recorder("train/g0").issued_calls()
    c1 = comm.recorder("train/g1").issued_calls()
    assert len(c0) == 1 and len(c1) == 1
    assert {n for _, n, _w in c0}.isdisjoint({n for _, n, _w in c1})
    assert not comm.recorder("train").issued_calls()
    # both buckets were in flight together: one shared window, pop 2
    (w0,), (w1,) = {w for *_, w in c0}, {w for *_, w in c1}
    assert w0 == w1
    assert comm.window_population(w0) == 2.0
    # the barrier closes the window: later issues get a FRESH one
    comm.await_barrier()
    with comm.recording(comm.recorder("train"), name="train"):
        with comm.issue_scope("g0"):
            comm.plan_for(AR, jnp.zeros((512, 512), jnp.float32))
    w2 = comm.recorder("train/g0").issued_calls()[-1][2]
    assert w2 != w0
    assert comm.window_population(w2) == 1.0
    # feeding Stage 2 the whole family does not blow up and prices each
    # call at its own window's population
    comm.observe_recorders(comm.family_recorders("train"))


def test_unregister_drops_issue_subrecorders():
    comm = comm_init_rank("x", 8, CommConfig(profile="h800"))
    comm.register_recorder("p")
    with comm.recording(comm.recorder("p"), name="p"):
        with comm.issue_scope("g0"):
            comm.plan_for(AR, jnp.zeros((64, 64), jnp.float32))
    assert "p/g0" in comm._recorders
    comm.unregister_recorder("p")
    assert "p/g0" not in comm._recorders and "p" not in comm._recorders


# ---------------------------------------------------------------------------
# contention pricing: serial case bitwise identical, k-way bounded
# ---------------------------------------------------------------------------

def test_contention_one_is_bitwise_identical():
    prof = PROFILES["h800"]
    shares = {prof.primary.name: 0.6}
    for link in prof.secondary:
        shares[link.name] = 0.4 / len(prof.secondary)
    a = PathTimingModel(prof).measure(AR, 8, 1 << 24, shares)
    b = PathTimingModel(prof).measure(AR, 8, 1 << 24, shares,
                                      contention=1.0)
    assert a == b                       # dict of floats, bitwise equality


def test_contention_scales_wire_time_not_latency():
    prof = PROFILES["h800"]
    shares = {prof.primary.name: 0.6}
    for link in prof.secondary:
        shares[link.name] = 0.4 / len(prof.secondary)
    t1 = PathTimingModel(prof).total_time(AR, 8, 1 << 26, shares)
    t2 = PathTimingModel(prof).total_time(AR, 8, 1 << 26, shares,
                                          contention=2.0)
    # halved bandwidth doubles the wire term but leaves latency alone
    assert t1 < t2 < 2.0 * t1


# ---------------------------------------------------------------------------
# StepProgram issue/await lifecycle
# ---------------------------------------------------------------------------

def _overlap_program(ctx, mesh, name):
    comm = ctx.comms()[0]

    def builder():
        def step(v):
            with ctx.issue("b0"):
                a = comm.all_reduce(v)
            with ctx.issue("b1"):
                b = comm.all_reduce(2.0 * v)
            return ctx.await_all(a + b)

        return jax.jit(shard_map(step, mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P("data"), check_vma=False))

    x = (np.arange(4 * 8, dtype=np.float32) % 5).reshape(4 * 8, 1)
    return StepProgram(builder, ctx, name=name), jnp.asarray(x)


def test_step_program_issue_await_lifecycle():
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
    ctx = ParallelCtx(dp_axis="data", dp_size=4,
                      comm_config=CommConfig(profile="tpu_v5e",
                                             tag="ov-prog"))
    prog, x = _overlap_program(ctx, mesh, "ovl")
    try:
        h = prog.issue(x)
        assert not h.ready and prog._pending == [h]
        outs = prog.await_all()
        assert h.ready and len(outs) == 1 and not prog._pending
        want = 3.0 * np.asarray(x).reshape(4, 8, 1).sum(0)
        np.testing.assert_array_equal(
            np.asarray(outs[0]).reshape(4, 8, 1)[0], want)
        comm = ctx.comms()[0]
        # the traced issue scopes registered per-bucket sub-recorders
        # sharing one window of population 2
        c0 = comm.recorder("ovl/b0").issued_calls()
        c1 = comm.recorder("ovl/b1").issued_calls()
        assert len(c0) == 1 and len(c1) == 1
        assert c0[0][2] == c1[0][2]
        assert comm.window_population(c0[0][2]) == 2.0
        # second round: signature hit -> no re-trace, logs replay as-is
        prog.issue(x)
        outs2 = prog.await_all()
        np.testing.assert_array_equal(np.asarray(outs2[0]),
                                      np.asarray(outs[0]))
        assert prog.cache.report()["hits"] >= 1
        # an await with nothing pending is a harmless barrier
        assert prog.await_all() == []
    finally:
        prog.close()


# ---------------------------------------------------------------------------
# fused metrics reduce
# ---------------------------------------------------------------------------

@needs8
def test_metrics_reduce_matches_nested_psums():
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("node", "data"))
    ctx = ParallelCtx(dp_axis="data", node_axis="node",
                      dp_size=4, node_size=2,
                      comm_config=CommConfig(profile="tpu_v5e",
                                             tag="ov-metrics"))
    x = (np.arange(8 * 6, dtype=np.float32) % 7).reshape(8 * 6, 1)
    spec = P(("node", "data"))

    def fused(v):
        return ctx.metrics_reduce({"loss": v.sum()},
                                  {"lr": jnp.float32(0.5)})

    def nested(v):
        return {"loss": ctx.pod_psum(ctx.node_psum(ctx.dp_psum(v.sum()))),
                "lr": jnp.float32(0.5)}

    def run(fn):
        f = shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=P(),
                      check_vma=False)
        return jax.tree.map(np.asarray, jax.jit(f)(x))

    got, want = run(fused), run(nested)
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=0, atol=0)
    assert got["lr"] == pytest.approx(0.5)


def test_metrics_reduce_passthrough_without_axes():
    ctx = ParallelCtx()
    out = ctx.metrics_reduce({"loss": jnp.float32(3.0)},
                             {"lr": jnp.float32(0.1)})
    assert float(out["loss"]) == 3.0 and float(out["lr"]) == \
        pytest.approx(0.1)


# ---------------------------------------------------------------------------
# overlap-aware roofline bounds
# ---------------------------------------------------------------------------

def test_step_time_bounds_bracket_and_degenerate():
    b1 = step_time_bounds(1.0, 0.5, 0.8, n_buckets=1)
    # monolithic: the two bounds coincide at the serial sum
    assert b1["t_step_overlap"] == b1["t_step_serial"] == 1.8
    b8 = step_time_bounds(1.0, 0.5, 0.8, n_buckets=8)
    assert b8["t_step_serial"] == b1["t_step_serial"]
    assert b8["t_step_overlap"] < b1["t_step_serial"]
    assert b8["t_step_overlap"] >= max(1.0, 0.8)
    assert b8["exposed_comm_s"] == pytest.approx(0.1)
    # comm-bound: overlap can never beat the collective term itself
    bc = step_time_bounds(0.1, 0.1, 1.0, n_buckets=4)
    assert bc["t_step_overlap"] >= 1.0
    # memory-bound side uses max(compute, memory)
    bm = step_time_bounds(0.2, 2.0, 0.5, n_buckets=4)
    assert bm["t_step_overlap"] == pytest.approx(
        max(2.0, 0.5 * 3 / 4) + 0.5 / 4)
