"""repro.cluster (DESIGN.md §9): topology model, two-tier hierarchical
collectives, per-tier control, and the N=1 degeneration contract.

Bit-exactness discipline: reductions associate differently per schedule,
so the property tests drive them with SMALL-INTEGER-valued payloads —
every partial sum is exactly representable in fp32 AND bf16, making any
summation order produce identical bits.  Pure data movement (all_gather)
is bit-exact for arbitrary values.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.cluster import (ClusterTimingModel, ClusterTopology, cluster_for,
                           make_cluster, nic_tier_name)
from repro.cluster.communicator import ClusterCommunicator
from repro.core.communicator import (CommConfig, FlexCommunicator,
                                     comm_destroy_all)
from repro.core.links import PROFILES, LinkKind, register_profile
from repro.core.topology import Collective

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 CPU devices")

AR, AG, RS = (Collective.ALL_REDUCE, Collective.ALL_GATHER,
              Collective.REDUCE_SCATTER)


@pytest.fixture(autouse=True)
def _fresh_comms():
    comm_destroy_all()
    yield
    comm_destroy_all()


# ---------------------------------------------------------------------------
# topology model
# ---------------------------------------------------------------------------

def test_make_cluster_registers_deterministic_nic_tier():
    topo = make_cluster("h800", 2, nics_per_node=4, nic_gbit=400.0)
    name = nic_tier_name("h800", 4, 400.0)
    assert topo.nic_tier.name == name
    assert PROFILES[name] is topo.nic_tier
    assert topo.nic_tier.tier == "inter"
    assert topo.nic_tier.primary.kind is LinkKind.NIC_RAIL
    assert topo.nic_tier.inter_hop_us > 0
    # re-building the same cluster resolves to the SAME registered profile
    again = make_cluster("h800", 4, nics_per_node=4, nic_gbit=400.0)
    assert again.nic_tier is topo.nic_tier


def test_register_profile_rejects_conflicting_name():
    import dataclasses
    topo = make_cluster("h800", 2)
    clash = dataclasses.replace(topo.nic_tier, inter_hop_us=99.0)
    with pytest.raises(ValueError):
        register_profile(clash)


def test_flatten_is_the_node_profile_and_rails_pair_up():
    topo = make_cluster("h800", 4, nics_per_node=4)
    assert topo.flatten() is PROFILES["h800"]
    assert topo.hierarchical and topo.tiers == ("intra", "inter")
    rings = topo.rail_rings()
    assert set(rings) == {0, 1, 2, 3}
    # rail-aligned: every rail forms the same node ring, no cross-rail edge
    assert all(r == [(0, 1), (1, 2), (2, 3), (3, 0)] for r in rings.values())
    single = make_cluster("h800", 1)
    assert not single.hierarchical and single.tiers == ("intra",)
    assert single.rail_rings()[0] == []


# ---------------------------------------------------------------------------
# analytic two-tier model: hierarchy vs flat ring
# ---------------------------------------------------------------------------

def test_hierarchy_beats_flat_ring_for_large_messages():
    topo = make_cluster("h800", 2, nics_per_node=4, nic_gbit=400.0)
    model = ClusterTimingModel(topo, 8)
    big = 256 * (1 << 20)
    for op in (AR, AG):
        assert model.hierarchical_time(op, big) < model.flat_time(op, big)
    # and the flat ring's single launch wins the latency-bound regime
    small = 64 * 1024
    assert model.flat_time(AR, small) < model.hierarchical_time(AR, small)
    xo = model.crossover_bytes(AR)
    assert xo is not None and small < xo <= big


def test_hierarchical_time_degenerates_per_tier():
    topo = make_cluster("h800", 1)
    m = ClusterTimingModel(topo, 8)
    b = 1 << 24
    assert m.hierarchical_time(AR, b) == m.tier_time("intra", AR, 8, b)
    topo2 = make_cluster("h800", 4)
    m2 = ClusterTimingModel(topo2, 1)
    assert m2.hierarchical_time(AR, b) == m2.tier_time("inter", AR, 4, b)


# ---------------------------------------------------------------------------
# N=1: the cluster path IS the single-node path (plan-for-plan parity)
# ---------------------------------------------------------------------------

@needs8
def test_n1_cluster_plan_parity_with_flat_single_node():
    """Acceptance: an N=1 ClusterCommunicator resolves the exact same
    quantized plans (same plan_signature()) as today's bare communicator,
    and executes bit-identically — the cluster path is a strict superset,
    not a fork."""
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4), ("data",))
    cfg_a = CommConfig(profile="tpu_v5e", tag="n1-flat")
    cfg_b = CommConfig(profile="tpu_v5e", tag="n1-cluster")
    flat = FlexCommunicator("data", 4, cfg_a)
    topo = make_cluster("tpu_v5e", 1, nics_per_node=2, nic_gbit=200.0)
    cc = ClusterCommunicator(topo, FlexCommunicator("data", 4, cfg_b), None)

    x = (np.arange(4 * 16 * 3) % 11).astype(np.float32).reshape(4 * 16, 3)

    def run(fn, out_spec=P("data")):
        f = shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                      out_specs=out_spec, check_vma=False)
        return np.asarray(jax.jit(f)(x))

    got_ar = run(cc.all_reduce)
    want_ar = run(flat.all_reduce)
    got_ag = run(lambda v: cc.all_gather(v, tiled=True), P())
    want_ag = run(lambda v: flat.all_gather(v, tiled=True), P())
    got_rs = run(cc.reduce_scatter)
    want_rs = run(flat.reduce_scatter)
    np.testing.assert_array_equal(got_ar, want_ar)
    np.testing.assert_array_equal(got_ag, want_ag)
    np.testing.assert_array_equal(got_rs, want_rs)
    # the plan-for-plan identity: same slots, same quantized plans
    assert cc.intra.plan_signature() == flat.plan_signature()
    assert cc.plan_signature() == (("data", flat.plan_signature()),)


# ---------------------------------------------------------------------------
# 2-node hierarchical collectives: bit-exact vs the flat reference
# ---------------------------------------------------------------------------

def _cluster_comm(mesh_nodes, ranks_per_node, tag):
    topo = make_cluster("h800", mesh_nodes)
    intra = (FlexCommunicator("data", ranks_per_node,
                              CommConfig(profile="h800",
                                         tag=f"{tag}-intra"))
             if ranks_per_node > 1 else None)
    inter = (FlexCommunicator("node", mesh_nodes,
                              CommConfig(profile=topo.nic_tier.name,
                                         tag=f"{tag}-inter"),
                              ortho_name="data" if ranks_per_node > 1
                              else None)
             if mesh_nodes > 1 else None)
    return ClusterCommunicator(topo, intra, inter)


def _mesh(n_nodes, ranks_per_node):
    devs = np.asarray(jax.devices()[:n_nodes * ranks_per_node])
    return Mesh(devs.reshape(n_nodes, ranks_per_node), ("node", "data"))


def _int_payload(shape, dtype, mod=7):
    # small integers: exactly representable in bf16, so ANY summation
    # order is bit-identical (module docstring)
    return (np.arange(int(np.prod(shape))) % mod).reshape(shape).astype(dtype)


@needs8
@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_hier_all_reduce_bit_exact_2x4(dtype):
    mesh = _mesh(2, 4)
    cc = _cluster_comm(2, 4, f"ar-{np.dtype(dtype).name}")
    x = _int_payload((8 * 24, 5), dtype)
    spec = P(("node", "data"))
    f = shard_map(cc.all_reduce, mesh=mesh, in_specs=(spec,),
                  out_specs=spec, check_vma=False)
    r = shard_map(lambda v: lax.psum(v, ("node", "data")), mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


@needs8
def test_hier_all_gather_node_major_order():
    mesh = _mesh(2, 4)
    cc = _cluster_comm(2, 4, "ag-order")
    x = np.random.default_rng(0).normal(size=(8 * 6, 3)).astype(np.float32)
    spec = P(("node", "data"))
    f = shard_map(lambda v: cc.all_gather(v, tiled=True), mesh=mesh,
                  in_specs=(spec,), out_specs=P(), check_vma=False)
    r = shard_map(lambda v: lax.all_gather(v, ("node", "data"), tiled=True),
                  mesh=mesh, in_specs=(spec,), out_specs=P(),
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


@needs8
def test_hier_reduce_scatter_interleaved_segments():
    """The documented shard-order contract: rank (node, i) holds global
    segment i * n_nodes + node of the flat reduction (intra-major
    interleaving — the bandwidth-optimal intra-first order)."""
    n, m = 2, 4
    mesh = _mesh(n, m)
    cc = _cluster_comm(n, m, "rs-order")
    x = _int_payload((8 * 8, 3), np.float32)
    spec = P(("node", "data"))

    def hier(v):
        return cc.reduce_scatter(v)

    def ref(v):
        red = lax.psum(v, ("node", "data"))
        node = lax.axis_index("node")
        i = lax.axis_index("data")
        seg = red.shape[0] // (n * m)
        return lax.dynamic_slice_in_dim(red, (i * n + node) * seg, seg, 0)

    f = shard_map(hier, mesh=mesh, in_specs=(spec,), out_specs=spec,
                  check_vma=False)
    r = shard_map(ref, mesh=mesh, in_specs=(spec,), out_specs=spec,
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


# ---------------------------------------------------------------------------
# property test: hierarchical == flat across node counts, ranks, dtypes
# ---------------------------------------------------------------------------

#: (n_nodes, ranks_per_node) pairs that fit the 8-device CPU backend.
_GRID = [(1, 2), (1, 4), (2, 2), (2, 4), (4, 2)]


@needs8
@settings(max_examples=20, deadline=None)
@given(layout=st.sampled_from(_GRID),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       cols=st.integers(min_value=1, max_value=5),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_hierarchical_matches_flat_reference(layout, dtype, cols, seed):
    n, m = layout
    mesh = _mesh(n, m)
    cc = _cluster_comm(n, m, f"prop-{n}x{m}")
    rng = np.random.default_rng(seed)
    rows = (n * m) * int(rng.integers(1, 4)) * 4
    x = rng.integers(0, 8, size=(rows, cols)).astype(np.float32)
    x = jnp.asarray(x).astype(dtype)
    spec = P(("node", "data"))

    fa = shard_map(cc.all_reduce, mesh=mesh, in_specs=(spec,),
                   out_specs=spec, check_vma=False)
    ra = shard_map(lambda v: lax.psum(v, ("node", "data")), mesh=mesh,
                   in_specs=(spec,), out_specs=spec, check_vma=False)
    got = np.asarray(jax.jit(fa)(x).astype(jnp.float32))
    want = np.asarray(jax.jit(ra)(x).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)

    fg = shard_map(lambda v: cc.all_gather(v, tiled=True), mesh=mesh,
                   in_specs=(spec,), out_specs=P(), check_vma=False)
    rg = shard_map(lambda v: lax.all_gather(v, ("node", "data"),
                                            tiled=True),
                   mesh=mesh, in_specs=(spec,), out_specs=P(),
                   check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(fg)(x).astype(jnp.float32)),
        np.asarray(jax.jit(rg)(x).astype(jnp.float32)))


# ---------------------------------------------------------------------------
# ctx integration: node axis, hierarchical grad sync, per-tier reporting
# ---------------------------------------------------------------------------

@needs8
def test_ctx_node_axis_hierarchical_grad_reduce():
    from repro.models.tp import ParallelCtx
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                ("node", "data", "model"))
    ctx = ParallelCtx(tp_axis="model", dp_axis="data", node_axis="node",
                      tp_size=2, dp_size=2, node_size=2,
                      comm_config=CommConfig(profile="tpu_v5e",
                                             tag="ctx-grad"))
    assert [c.axis_name for c in ctx.comms()] == ["model", "data", "node"]
    assert ctx.cluster.nic_tier.name in PROFILES
    x = _int_payload((8 * 16, 3), np.float32)
    spec = P(("node", "data"))

    def red(v):
        return ctx.grad_all_reduce({"w": v})["w"]

    f = shard_map(red, mesh=mesh, in_specs=(spec,), out_specs=spec,
                  check_vma=False)
    r = shard_map(lambda v: lax.psum(v, ("node", "data")), mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))
    # the signature spans all three axes — the NIC tier re-keys programs
    # like any other slot set
    assert [s[0] for s in ctx.plan_signature()] == ["model", "data", "node"]
    rep = ctx.comm_report()
    assert rep["node"]["tier"] == "inter"
    assert rep["data"]["tier"] == "intra"
    roll = rep["cluster"]["rollup"]
    assert set(roll) == {"intra", "inter"} and roll["inter"]["slots"] >= 1


@needs8
def test_ctx_node_axis_without_dp_uses_inter_tier_only():
    from repro.models.tp import ParallelCtx
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(4, 1, 1),
                ("node", "data", "model"))
    ctx = ParallelCtx(node_axis="node", node_size=4,
                      comm_config=CommConfig(profile="tpu_v5e",
                                             tag="ctx-inter-only"))
    assert ctx._cluster_comm is not None
    assert not ctx._cluster_comm.hierarchical
    x = _int_payload((32, 2), np.float32)
    f = shard_map(lambda v: ctx.grad_all_reduce({"w": v})["w"], mesh=mesh,
                  in_specs=(P("node"),), out_specs=P("node"),
                  check_vma=False)
    r = shard_map(lambda v: lax.psum(v, "node"), mesh=mesh,
                  in_specs=(P("node"),), out_specs=P("node"),
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


def test_cluster_for_and_named_presets_agree():
    from repro.configs.clusters import CLUSTER_IDS, get_cluster
    auto = cluster_for("tpu_v5e", 2)
    named = get_cluster("2xtpu_v5e_dcn")
    assert auto.nic_tier is named.nic_tier     # same registered tier
    assert "2xh800_rail4" in CLUSTER_IDS
    with pytest.raises(KeyError):
        get_cluster("nonexistent")


# ---------------------------------------------------------------------------
# end to end: a cluster-mesh train run matches the flat single-node run
# ---------------------------------------------------------------------------

@needs8
def test_multi_node_train_matches_single_node():
    """Same model, same global batch, same total DP degree: training on a
    (node=2, data=2, model=2) cluster mesh — hierarchical gradient sync
    through the NIC tier — must be numerically equivalent to the flat
    (data=4, model=2) single-node mesh."""
    from repro.configs import get_config
    from repro.data.pipeline import make_batches
    from repro.launch import shapes as SH
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_train_step
    from repro.models import init_params
    from repro.optim.adamw import AdamWConfig, init_state

    key = jax.random.PRNGKey(0)
    out = {}
    for name, dims, axes in (("flat", (4, 2), ("data", "model")),
                             ("cluster", (2, 2, 2),
                              ("node", "data", "model"))):
        comm_destroy_all()
        cfg = get_config("glm4-9b").reduced()
        mesh = make_mesh(dims, axes)
        shape = SH.InputShape("t", "train", 32, 4)
        comm = CommConfig(profile="tpu_v5e", tag=f"e2e-{name}")
        step, ctx = build_train_step(
            cfg, mesh, comm=comm, shape=shape,
            opt=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20))
        if name == "cluster":
            assert ctx.node_size == 2 and ctx._cluster_comm is not None
        params = init_params(key, cfg)
        opt_state = init_state(params)
        batches = make_batches(cfg, seq_len=32, batch_per_shard=4, seed=7)
        losses = []
        with mesh:
            for _ in range(4):
                params, opt_state, m = step(
                    params, opt_state,
                    {k: jnp.asarray(v) for k, v in next(batches).items()})
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        out[name] = losses
    np.testing.assert_allclose(out["flat"], out["cluster"], atol=5e-3)


@needs8
def test_ctx_rejects_cluster_profile_mismatch():
    """A named cluster built from different nodes than the comm profile
    must be rejected, not silently half-applied (reports and warm-start
    keys would describe a fabric that never ran)."""
    from repro.models.tp import ParallelCtx
    topo = make_cluster("h800", 2)
    with pytest.raises(ValueError, match="fabric that never ran"):
        ParallelCtx(dp_axis="data", dp_size=2, node_axis="node",
                    node_size=2, cluster=topo,
                    comm_config=CommConfig(profile="tpu_v5e",
                                           tag="mismatch"))
