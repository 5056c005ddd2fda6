"""FlexCommunicator (control plane + NCCL-shaped API) integration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.communicator import (CommConfig, FlexCommunicator,
                                     bucket_for, comm_destroy_all,
                                     comm_init_rank)
from repro.core.topology import Collective

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 CPU devices")


def mesh2d():
    devs = np.asarray(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("x", "y"))


@pytest.fixture(autouse=True)
def _fresh_comms():
    comm_destroy_all()
    yield
    comm_destroy_all()


def test_stage1_runs_once_per_bucket():
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    r1 = comm.tune(Collective.ALL_GATHER, 256 * 2**20)
    r2 = comm.tune(Collective.ALL_GATHER, 255 * 2**20)  # same bucket
    assert r1 is r2
    r3 = comm.tune(Collective.ALL_GATHER, 8 * 2**20)    # different bucket
    assert r3 is not r1


def test_shares_keyed_by_route_class():
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    shares = comm.shares_for(Collective.ALL_GATHER, 256 * 2**20)
    assert "primary" in shares
    assert sum(shares.values()) == 100


def test_nccl_mode_single_path():
    comm = FlexCommunicator("x", 8, CommConfig(backend="nccl",
                                               profile="h800"))
    shares = comm.shares_for(Collective.ALL_GATHER, 256 * 2**20)
    assert shares == {"primary": 100}


def test_all_reduce_through_communicator():
    mesh = mesh2d()
    comm = FlexCommunicator("x", 4, CommConfig(profile="h800"),
                            ortho_name="y")
    x = jnp.arange(4 * 6, dtype=jnp.float32).reshape(4 * 6) * 0.5

    def step(xs):
        return comm.all_reduce(xs)

    f = shard_map(step, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
                  check_vma=False)
    r = shard_map(lambda xs: lax.psum(xs, "x"), mesh=mesh, in_specs=(P("x"),),
                  out_specs=P("x"), check_vma=False)
    np.testing.assert_allclose(np.asarray(jax.jit(f)(x)),
                               np.asarray(jax.jit(r)(x)), rtol=1e-6)


def test_all_gather_through_communicator():
    mesh = mesh2d()
    comm = FlexCommunicator("x", 4, CommConfig(profile="h800"),
                            ortho_name="y")
    x = jnp.arange(4 * 3 * 2, dtype=jnp.float32).reshape(4 * 3, 2)

    f = shard_map(lambda xs: comm.all_gather(xs), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P(), check_vma=False)
    r = shard_map(lambda xs: lax.all_gather(xs, "x", tiled=True), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P(), check_vma=False)
    np.testing.assert_array_equal(np.asarray(jax.jit(f)(x)),
                                  np.asarray(jax.jit(r)(x)))


def test_broadcast():
    mesh = mesh2d()
    comm = FlexCommunicator("x", 4, CommConfig(profile="h800"))
    x = jnp.arange(4 * 2, dtype=jnp.float32).reshape(4 * 2)

    f = shard_map(lambda xs: comm.broadcast(xs, root=2), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    got = np.asarray(jax.jit(f)(x)).reshape(4, 2)
    want = np.tile(np.asarray(x).reshape(4, 2)[2], (4, 1))
    np.testing.assert_array_equal(got, want)


def test_runtime_balancing_reacts_to_size():
    """Decode-sized messages -> balancer walks secondary shares down."""
    cfg = CommConfig(profile="h800", runtime_balancing=True)
    comm = FlexCommunicator("x", 8, cfg)
    big = comm.shares_for(Collective.ALL_GATHER, 256 * 2**20)
    sec_before = 100 - big.get("primary", 0)
    # hammer the small bucket: latency dominates, Stage 2 trims secondaries
    for _ in range(300):
        comm.record_call(Collective.ALL_GATHER, 1 * 2**20)
    small = comm.shares_for(Collective.ALL_GATHER, 1 * 2**20)
    assert small.get("primary", 0) >= big.get("primary", 0)
    assert sum(small.values()) == 100
    assert sec_before >= 0


def test_comm_registry_memoizes():
    a = comm_init_rank("x", 8)
    b = comm_init_rank("x", 8)
    assert a is b
    c = comm_init_rank("x", 8, CommConfig(backend="nccl"))
    assert c is not a


def test_report_contains_prediction():
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    comm.tune(Collective.ALL_GATHER, 256 * 2**20)
    rep = comm.report()
    cache = rep.pop("plan_cache")
    assert set(cache) >= {"hits", "misses", "retraces", "size"}
    assert rep.pop("timing_source") == "sim"
    assert rep.pop("tier") == "intra"
    rollup = rep.pop("rollup")
    assert rollup == {"intra": {"slots": 1, "warm": 0, "converged": 1,
                                "stage2_adjustments": 0, "probes": 0,
                                "member_moves": 0, "drained_members": 0,
                                "compressed_slots": 0,
                                "offloaded_bytes_saved": 0}}
    (key, entry), = rep.items()
    assert entry["predicted_algbw_GBps"] >= entry["nccl_algbw_GBps"] * 0.98
    assert entry["converged"]


@pytest.mark.parametrize("root", [0, 1, 3])
def test_broadcast_any_root(root):
    mesh = mesh2d()
    comm = FlexCommunicator("x", 4, CommConfig(profile="h800"))
    x = (jnp.arange(4 * 3 * 2, dtype=jnp.float32).reshape(4 * 3, 2)
         * 0.5 - 1.0)

    f = shard_map(lambda xs: comm.broadcast(xs, root=root), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    got = np.asarray(jax.jit(f)(x)).reshape(4, 3, 2)
    want = np.tile(np.asarray(x).reshape(4, 3, 2)[root], (4, 1, 1))
    np.testing.assert_array_equal(got, want)


def test_broadcast_preserves_dtype_and_shape():
    mesh = mesh2d()
    comm = FlexCommunicator("x", 4, CommConfig(profile="h800"))
    x = jnp.arange(4 * 2, dtype=jnp.int32).reshape(4 * 2)
    f = shard_map(lambda xs: comm.broadcast(xs, root=1), mesh=mesh,
                  in_specs=(P("x"),), out_specs=P("x"), check_vma=False)
    out = jax.jit(f)(x)
    assert out.dtype == x.dtype and out.shape == x.shape


def test_observe_executed_step_replays_issued_calls():
    """The host-side Stage-2 hook replays traced calls into the balancer and
    reports whether any share moved (-> caller re-traces)."""
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    x = jnp.zeros((512, 512), jnp.float32)
    comm.plan_for(Collective.ALL_GATHER, x)
    assert comm.issued_calls()
    changed = False
    for _ in range(40):                     # enough windows to trigger moves
        changed |= comm.observe_executed_step()
    assert isinstance(changed, bool)
    comm.reset_issued()
    assert not comm.issued_calls()
