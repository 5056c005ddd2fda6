"""repro.cluster pod tier (DESIGN.md §15): three-tier topology model,
pod-level hierarchical collectives, the rail-local ep_a2a dispatch, and
the pods=1 degeneration contract.

Same bit-exactness discipline as tests/test_cluster.py: reductions run
on SMALL-INTEGER payloads (every partial sum exact in fp32 AND bf16, so
any association is bit-identical); pure data movement (all_gather,
all_to_all) is bit-exact for arbitrary values.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.cluster import (ClusterTimingModel, make_cluster, pod_tier_name)
from repro.cluster.communicator import ClusterCommunicator
from repro.cluster.topology import degrade_cluster
from repro.core.communicator import (CommConfig, FlexCommunicator,
                                     bucket_for, comm_destroy_all)
from repro.core.links import PROFILES, LinkKind
from repro.core.simulator import MiB
from repro.core.topology import Collective

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 CPU devices")

AR, AG, RS, A2A = (Collective.ALL_REDUCE, Collective.ALL_GATHER,
                   Collective.REDUCE_SCATTER, Collective.ALL_TO_ALL)
EP_AXES = ("pod", "node", "data")


@pytest.fixture(autouse=True)
def _fresh_comms():
    comm_destroy_all()
    yield
    comm_destroy_all()


def _pod_cluster(pods, nodes):
    return make_cluster("h800", nodes, nics_per_node=4, nic_gbit=400.0,
                        pods=pods, pod_uplinks=4, pod_gbit=400.0)


def _comm3(p, n, m, tag):
    """One ClusterCommunicator over a (pod=p, node=n, data=m) mesh —
    tiers of size 1 are simply absent, like the launchers build them."""
    topo = _pod_cluster(p, n)
    intra = (FlexCommunicator("data", m,
                              CommConfig(profile="h800",
                                         tag=f"{tag}-intra"))
             if m > 1 else None)
    inter = (FlexCommunicator("node", n,
                              CommConfig(profile=topo.nic_tier.name,
                                         tag=f"{tag}-inter"),
                              ortho_name="data" if m > 1 else None)
             if n > 1 else None)
    pod = (FlexCommunicator("pod", p,
                            CommConfig(profile=topo.pod_tier.name,
                                       tag=f"{tag}-pod"),
                            ortho_name="node" if n > 1 else None)
           if p > 1 else None)
    return ClusterCommunicator(topo, intra, inter, pod)


def _mesh3(p, n, m):
    devs = np.asarray(jax.devices()[:p * n * m])
    return Mesh(devs.reshape(p, n, m), EP_AXES)


def _int_payload(shape, dtype, mod=7):
    return (np.arange(int(np.prod(shape))) % mod).reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# topology model: the pod tier is a registered NodeProfile like any other
# ---------------------------------------------------------------------------

def test_make_cluster_registers_deterministic_pod_tier():
    topo = _pod_cluster(2, 2)
    name = pod_tier_name("h800", 4, 400.0, 4.0)
    assert topo.n_pods == 2
    assert topo.pod_tier.name == name
    assert PROFILES[name] is topo.pod_tier
    assert topo.pod_tier.tier == "pod"
    assert topo.pod_tier.primary.kind is LinkKind.DCN_SPINE
    assert [m.name for m in topo.pod_tier.primary.members] == \
        [f"spine{i}" for i in range(4)]
    assert topo.tiers == ("intra", "inter", "pod")
    # re-building resolves to the SAME registered profile
    again = _pod_cluster(4, 2)
    assert again.pod_tier is topo.pod_tier


def test_oversubscription_divides_spine_bandwidth():
    lean = make_cluster("h800", 2, pods=2, pod_uplinks=4, pod_gbit=400.0,
                        oversubscription=1.0)
    fat = make_cluster("h800", 2, pods=2, pod_uplinks=4, pod_gbit=400.0,
                       oversubscription=4.0)
    assert lean.pod_tier.name != fat.pod_tier.name
    assert lean.pod_tier.primary.raw_GBps == pytest.approx(
        4.0 * fat.pod_tier.primary.raw_GBps)


def test_pods1_is_the_two_tier_topology_pinned():
    """The hard parity contract (DESIGN.md §15): pods=1 builds the exact
    2-tier object — same name, same tiers, NO pod profile — so every
    plan key, tuning entry and report of a pre-pod run is reproduced."""
    base = make_cluster("h800", 2, nics_per_node=4, nic_gbit=400.0)
    one = make_cluster("h800", 2, nics_per_node=4, nic_gbit=400.0, pods=1)
    assert one.pod_tier is None
    assert one.n_pods == 1
    assert one.name == base.name
    assert one.tiers == base.tiers == ("intra", "inter")
    assert one.nic_tier is base.nic_tier
    assert one == base


def test_degrade_cluster_routes_spine_faults_to_pod_tier():
    topo = _pod_cluster(2, 2)
    bad = degrade_cluster(topo, "spine:spine2=0.25")
    assert bad.name.endswith("!spine:spine2=0.25")
    assert bad.pod_tier.name != topo.pod_tier.name
    assert bad.nic_tier is topo.nic_tier          # NIC tier untouched
    # a rail fault still lands on the NIC tier, not the pod tier
    bad2 = degrade_cluster(topo, "rail:rail3=0.25")
    assert bad2.pod_tier is topo.pod_tier


# ---------------------------------------------------------------------------
# analytic model: three-tier time, rail-local a2a pricing
# ---------------------------------------------------------------------------

def test_three_tier_hierarchy_beats_flat_ring_for_large_messages():
    model = ClusterTimingModel(_pod_cluster(2, 2), 8)
    big = 256 * int(MiB)
    for op in (AR, AG):
        assert model.hierarchical_time(op, big) < model.flat_time(op, big)


def test_pods1_timing_is_the_two_tier_model():
    b = 1 << 24
    two = ClusterTimingModel(make_cluster("h800", 2), 8)
    one = ClusterTimingModel(make_cluster("h800", 2, pods=1), 8)
    for op in (AR, AG, RS):
        assert one.hierarchical_time(op, b) == two.hierarchical_time(op, b)
        assert one.flat_time(op, b) == two.flat_time(op, b)


def test_rail_local_a2a_beats_flat_and_naive_when_bandwidth_bound():
    model = ClusterTimingModel(_pod_cluster(4, 4), 8)
    big = 64 * int(MiB)
    rail = model.a2a_time(big, schedule="rail_local")
    assert rail < model.a2a_time(big, schedule="flat")
    assert rail < model.a2a_time(big, schedule="naive")
    with pytest.raises(ValueError):
        model.a2a_time(big, schedule="bogus")


# ---------------------------------------------------------------------------
# pods=1: the cluster comm path is byte-identical with the pod code present
# ---------------------------------------------------------------------------

@needs8
def test_pods1_cluster_comm_signature_parity_pinned():
    """Acceptance: a pods=1 ClusterCommunicator resolves the exact same
    quantized plans (pinned ``==`` on plan_signature()) and executes
    bit-identically to the 2-tier communicator — the pod tier is a
    strict superset, not a fork of the 2-tier path."""
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("node", "data"))

    def two_tier(tag, topo):
        intra = FlexCommunicator("data", 4, CommConfig(
            profile="h800", tag=f"{tag}-intra"))
        inter = FlexCommunicator("node", 2, CommConfig(
            profile=topo.nic_tier.name, tag=f"{tag}-inter"),
            ortho_name="data")
        return ClusterCommunicator(topo, intra, inter)

    cc_a = two_tier("par-a", make_cluster("h800", 2))
    cc_b = two_tier("par-b", make_cluster("h800", 2, pods=1))
    assert cc_b.pod is None and cc_b.comms() == (cc_b.intra, cc_b.inter)

    x = _int_payload((8 * 16, 3), np.float32)
    spec = P(("node", "data"))
    for fn_a, fn_b, out_spec in (
            (cc_a.all_reduce, cc_b.all_reduce, spec),
            (lambda v: cc_a.all_gather(v, tiled=True),
             lambda v: cc_b.all_gather(v, tiled=True), P()),
            (cc_a.reduce_scatter, cc_b.reduce_scatter, spec)):
        fa = shard_map(fn_a, mesh=mesh, in_specs=(spec,),
                       out_specs=out_spec, check_vma=False)
        fb = shard_map(fn_b, mesh=mesh, in_specs=(spec,),
                       out_specs=out_spec, check_vma=False)
        np.testing.assert_array_equal(np.asarray(jax.jit(fa)(x)),
                                      np.asarray(jax.jit(fb)(x)))
    assert cc_a.plan_signature() == cc_b.plan_signature()


# ---------------------------------------------------------------------------
# three-tier collectives: bit-exact vs the flat reference
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_tier_all_reduce_bit_exact_2x2x2(dtype):
    mesh = _mesh3(2, 2, 2)
    cc = _comm3(2, 2, 2, f"ar3-{dtype}")
    x = jnp.asarray(_int_payload((8 * 24, 5), np.float32)).astype(dtype)
    spec = P(EP_AXES)
    f = shard_map(cc.all_reduce, mesh=mesh, in_specs=(spec,),
                  out_specs=spec, check_vma=False)
    r = shard_map(lambda v: lax.psum(v, EP_AXES), mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(f)(x).astype(jnp.float32)),
        np.asarray(jax.jit(r)(x).astype(jnp.float32)))


@needs8
def test_three_tier_all_gather_outermost_major_order():
    mesh = _mesh3(2, 2, 2)
    cc = _comm3(2, 2, 2, "ag3-order")
    x = np.random.default_rng(0).normal(size=(8 * 6, 3)).astype(np.float32)
    spec = P(EP_AXES)
    f = shard_map(lambda v: cc.all_gather(v, tiled=True), mesh=mesh,
                  in_specs=(spec,), out_specs=P(), check_vma=False)
    r = shard_map(lambda v: lax.all_gather(v, EP_AXES, tiled=True),
                  mesh=mesh, in_specs=(spec,), out_specs=P(),
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


@needs8
def test_three_tier_reduce_scatter_segment_contract():
    """The documented shard-order contract one level up: rank
    (pod, node, i) holds global segment ``(i * n + node) * p + pod`` of
    the flat reduction (innermost-major interleaving)."""
    p, n, m = 2, 2, 2
    mesh = _mesh3(p, n, m)
    cc = _comm3(p, n, m, "rs3-order")
    x = _int_payload((8 * 8, 3), np.float32)
    spec = P(EP_AXES)

    def ref(v):
        red = lax.psum(v, EP_AXES)
        pod = lax.axis_index("pod")
        node = lax.axis_index("node")
        i = lax.axis_index("data")
        seg = red.shape[0] // (p * n * m)
        return lax.dynamic_slice_in_dim(
            red, ((i * n + node) * p + pod) * seg, seg, 0)

    f = shard_map(cc.reduce_scatter, mesh=mesh, in_specs=(spec,),
                  out_specs=spec, check_vma=False)
    r = shard_map(ref, mesh=mesh, in_specs=(spec,), out_specs=spec,
                  check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


# ---------------------------------------------------------------------------
# property test: three-tier == flat across layouts and dtypes
# ---------------------------------------------------------------------------

#: (pods, nodes_per_pod, ranks_per_node) triples on the 8-device backend,
#: covering absent intra (m=1), absent inter (n=1) and all-live tiers.
_GRID3 = [(2, 2, 2), (2, 1, 4), (2, 4, 1), (4, 2, 1), (4, 1, 2)]


@needs8
@settings(max_examples=20, deadline=None)
@given(layout=st.sampled_from(_GRID3),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       cols=st.integers(min_value=1, max_value=5),
       seed=st.integers(min_value=0, max_value=2 ** 16))
def test_three_tier_matches_flat_reference(layout, dtype, cols, seed):
    p, n, m = layout
    mesh = _mesh3(p, n, m)
    cc = _comm3(p, n, m, f"prop3-{p}x{n}x{m}")
    rng = np.random.default_rng(seed)
    rows = (p * n * m) * int(rng.integers(1, 4)) * 4
    x = rng.integers(0, 8, size=(rows, cols)).astype(np.float32)
    x = jnp.asarray(x).astype(dtype)
    spec = P(EP_AXES)

    fa = shard_map(cc.all_reduce, mesh=mesh, in_specs=(spec,),
                   out_specs=spec, check_vma=False)
    ra = shard_map(lambda v: lax.psum(v, EP_AXES), mesh=mesh,
                   in_specs=(spec,), out_specs=spec, check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(fa)(x).astype(jnp.float32)),
        np.asarray(jax.jit(ra)(x).astype(jnp.float32)))

    fg = shard_map(lambda v: cc.all_gather(v, tiled=True), mesh=mesh,
                   in_specs=(spec,), out_specs=P(), check_vma=False)
    rg = shard_map(lambda v: lax.all_gather(v, EP_AXES, tiled=True),
                   mesh=mesh, in_specs=(spec,), out_specs=P(),
                   check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(fg)(x).astype(jnp.float32)),
        np.asarray(jax.jit(rg)(x).astype(jnp.float32)))


# ---------------------------------------------------------------------------
# rail-local ep_a2a: bit-exact vs the flat all_to_all
# ---------------------------------------------------------------------------

@needs8
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ep_a2a_bit_exact_vs_flat_all_to_all(dtype):
    """The MoE dispatch contract: the rail-local decomposition must
    equal the flat all_to_all over the combined (pod, node, data) axes
    bit for bit — a2a is pure data movement, so arbitrary values."""
    mesh = _mesh3(2, 2, 2)
    cc = _comm3(2, 2, 2, f"a2a3-{dtype}")
    x = np.random.default_rng(3).normal(size=(8 * 16, 3)).astype(np.float32)
    x = jnp.asarray(x).astype(dtype)
    spec = P(EP_AXES)
    f = shard_map(lambda v: cc.ep_all_to_all(v, 0, 0), mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    r = shard_map(lambda v: lax.all_to_all(v, EP_AXES, 0, 0, tiled=True),
                  mesh=mesh, in_specs=(spec,), out_specs=spec,
                  check_vma=False)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(f)(x).astype(jnp.float32)),
        np.asarray(jax.jit(r)(x).astype(jnp.float32)))


@needs8
def test_ep_a2a_two_tier_matches_flat_dp_all_to_all():
    """With no pod tier the same decomposition (intra shuffle + rail-
    aligned node leg) must still equal the flat dp-style all_to_all over
    (node, data) — the 2-tier degeneration of the dispatch."""
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("node", "data"))
    topo = make_cluster("h800", 2)
    intra = FlexCommunicator("data", 4, CommConfig(profile="h800",
                                                   tag="a2a2-intra"))
    inter = FlexCommunicator("node", 2, CommConfig(
        profile=topo.nic_tier.name, tag="a2a2-inter"), ortho_name="data")
    cc = ClusterCommunicator(topo, intra, inter)
    x = np.random.default_rng(5).normal(size=(8 * 8, 2)).astype(np.float32)
    spec = P(("node", "data"))
    f = shard_map(lambda v: cc.ep_all_to_all(v, 0, 0), mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    r = shard_map(
        lambda v: lax.all_to_all(v, ("node", "data"), 0, 0, tiled=True),
        mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


@needs8
def test_ep_a2a_reports_rail_local_bytes():
    mesh = _mesh3(2, 2, 2)
    cc = _comm3(2, 2, 2, "a2a3-report")
    x = np.random.default_rng(7).normal(size=(8 * 16, 3)).astype(np.float32)
    spec = P(EP_AXES)
    f = shard_map(lambda v: cc.ep_all_to_all(v, 0, 0), mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    jax.block_until_ready(jax.jit(f)(x))
    rep = cc.a2a_report()
    assert rep["intra_bytes"] > 0
    assert rep["rail_local_bytes"] + rep["spine_bytes"] > 0
    s = cc.summary()
    assert set(s["rollup"]) == {"intra", "inter", "pod"}
    assert s["a2a"]["rail_local_bytes"] == rep["rail_local_bytes"]


# ---------------------------------------------------------------------------
# ctx integration: ep span over (pod, node, data), three-tier grad sync
# ---------------------------------------------------------------------------

@needs8
def test_ctx_pod_axis_three_tier_grad_reduce_and_ep_span():
    from repro.models.tp import ParallelCtx
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2, 1),
                ("pod", "node", "data", "model"))
    ctx = ParallelCtx(tp_axis="model", dp_axis="data", node_axis="node",
                      pod_axis="pod", tp_size=1, dp_size=2, node_size=2,
                      pod_size=2,
                      comm_config=CommConfig(profile="h800",
                                             tag="ctx-pod"))
    assert ctx._pod_comm is not None
    assert ctx.cluster.n_pods == 2
    assert ctx.ep_axes == EP_AXES and ctx.ep_size == 8
    assert ctx.ep_spec_axis() == EP_AXES
    assert [c.axis_name for c in ctx.comms()] == ["data", "node", "pod"]

    x = _int_payload((8 * 16, 3), np.float32)
    spec = P(EP_AXES)
    f = shard_map(lambda v: ctx.grad_all_reduce({"w": v})["w"], mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    r = shard_map(lambda v: lax.psum(v, EP_AXES), mesh=mesh,
                  in_specs=(spec,), out_specs=spec, check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))
    assert [s[0] for s in ctx.plan_signature()] == ["data", "node", "pod"]
    rep = ctx.comm_report()
    assert rep["pod"]["tier"] == "pod"
    roll = rep["cluster"]["rollup"]
    assert set(roll) == {"intra", "inter", "pod"}
    assert roll["pod"]["slots"] >= 1


# ---------------------------------------------------------------------------
# faults on the pod tier: spine events transition like any other tier
# ---------------------------------------------------------------------------

def test_spine_fault_transition_rekeys_pod_comm_warm(tmp_path):
    """A spine uplink fault commits one hysteresis-gated transition on
    the pod-tier communicator and re-keys it WARM from the degraded
    fabric's cached tune — PR 9's machinery, one tier up, for free."""
    from repro.faults import (FabricClock, HealthTimeline, HYSTERESIS_K,
                              parse_fault_schedule, validate_schedule)
    cluster = _pod_cluster(2, 2)
    tier = cluster.pod_tier
    degraded = degrade_cluster(cluster, "spine:spine2=0.25")
    cache = str(tmp_path / "tuning.json")
    payload = int(16 * MiB)

    for prof in (degraded.pod_tier.name, tier.name):
        c = FlexCommunicator("pod", 2, CommConfig(profile=prof,
                                                  tuning_cache=cache))
        for _ in range(12):
            c.record_call(AR, payload)
        c.save_tuning(cache)
    comm_destroy_all()

    tl = HealthTimeline(validate_schedule(
        parse_fault_schedule("spine:spine2@step10=0.25"),
        profiles=[cluster.nic_tier, tier], n_nodes=2))
    comm = FlexCommunicator("pod", 2, CommConfig(
        profile=tier.name, tuning_cache=cache, fault=tl.spec()))
    clock = FabricClock(tl, comms=lambda: [comm])
    committed = []
    for step in range(30):
        committed += clock.advance(step)
        comm.record_call(AR, payload)
    assert clock.rekeys == 1 and len(committed) == 1
    tr = committed[0]
    assert tr["step"] == 10 + HYSTERESIS_K - 1
    assert comm._effective_profile == degraded.pod_tier.name
    sc = comm.slot(AR, bucket_for(payload))
    assert sc.warm and sc.tuned.iterations == 0
    assert sc.origin == "transition:exact"


def test_resolve_faults_validates_spine_targets_against_pod_tier():
    from repro.configs.clusters import resolve_faults
    cluster = _pod_cluster(2, 2)
    # a spine target resolves only when the pod tier is in play
    _, _, tl = resolve_faults(cluster, 2, "h800",
                              fault="spine:spine2@step10=0.25", pods=2)
    assert tl is not None
    flat = make_cluster("h800", 2)
    with pytest.raises(ValueError, match="spine2"):
        resolve_faults(flat, 2, "h800",
                       fault="spine:spine2@step10=0.25")
