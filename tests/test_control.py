"""Control-plane tests (DESIGN.md §8): SlotController delegation,
TuningProfile warm-start round-trip, and the TimingSource seam — the sim
source must be bit-identical to the pre-control-plane behavior, and the
measured source must balance on wall-clock-derived timings with the
simulator consulted for bootstrap/apportionment weights only."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.control import (MeasuredTimingSource, SimTimingSource,
                           SlotController, TuningProfile)
from repro.core.balancer import LoadBalancer
from repro.core.communicator import (CommConfig, FlexCommunicator,
                                     bucket_for, comm_destroy_all,
                                     comm_init_rank)
from repro.core.simulator import MiB, PathTimingModel
from repro.core.topology import Collective
from repro.core.tuner import SHARE_GRID, initial_tune
from repro.models.tp import ParallelCtx
from repro.runtime.program import StepProgram

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 CPU devices")

AG, AR = Collective.ALL_GATHER, Collective.ALL_REDUCE


@pytest.fixture(autouse=True)
def _fresh_comms():
    comm_destroy_all()
    yield
    comm_destroy_all()


# ---------------------------------------------------------------------------
# SimTimingSource: behavior-preserving default
# ---------------------------------------------------------------------------

def test_sim_source_stage1_parity_with_pre_refactor():
    """A cold communicator's Stage-1 result must equal running Algorithm 1
    directly against the simulator — exactly what the pre-control-plane
    tune() did (same measure closure, same bucket payload)."""
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    got = comm.tune(AG, 256 * MiB)
    model = PathTimingModel("h800", noise=0.0, seed=0)
    bucket = bucket_for(256 * MiB)
    ref = initial_tune(["nvlink", "pcie", "rdma"], "nvlink",
                       lambda fr: model.measure(AG, 8, bucket, fr))
    assert got.shares == ref.shares
    assert got.iterations == ref.iterations
    assert got.converged == ref.converged


def test_sim_source_stage2_parity_with_pre_refactor():
    """record_call through the TimingSource seam must walk the shares to
    the same place the old inline ``model.measure`` loop did."""
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    for _ in range(200):
        comm.record_call(AG, 8 * MiB)

    model = PathTimingModel("h800", noise=0.0, seed=0)
    bucket = bucket_for(8 * MiB)
    ref = initial_tune(["nvlink", "pcie", "rdma"], "nvlink",
                       lambda fr: model.measure(AG, 8, bucket, fr))
    bal = LoadBalancer(ref.shares, "nvlink")
    for _ in range(200):
        bal.observe(model.measure(AG, 8, 8 * MiB, bal.fractions()))
    sc = comm.slot(AG, bucket)
    assert sc.balancer.shares == bal.shares
    assert len(sc.balancer.adjustments) == len(bal.adjustments)


def test_secondary_algo_reaches_the_timing_model():
    """CommConfig.secondary_algo (paper §6) must plumb into
    PathTimingModel — previously only constructible inside
    benchmarks/future_tree_allreduce.py."""
    tree = FlexCommunicator("x", 8, CommConfig(profile="h800",
                                               secondary_algo="tree"))
    ring = FlexCommunicator("y", 8, CommConfig(profile="h800"))
    assert tree.model.secondary_algo == "tree"
    assert ring.model.secondary_algo == "ring"
    # and it changes the tuned outcome where the paper predicts it would:
    # 8-rank AllReduce, where ring secondaries die of latency
    t_res = tree.tune(AR, 256 * MiB)
    r_res = ring.tune(AR, 256 * MiB)
    t_sec = SHARE_GRID - t_res.shares["nvlink"]
    r_sec = SHARE_GRID - r_res.shares["nvlink"]
    assert t_sec > r_sec


# ---------------------------------------------------------------------------
# TuningProfile store
# ---------------------------------------------------------------------------

def test_profile_record_save_load_lookup(tmp_path):
    path = str(tmp_path / "prof.json")
    prof = TuningProfile()
    prof.record("h800", "ring", AG, 8, 1 << 20, 100,
                {"nvlink": 80, "pcie": 13, "rdma": 7}, iterations=9)
    prof.save(path)
    loaded = TuningProfile.load(path)
    assert len(loaded) == 1
    assert loaded.lookup("h800", "ring", AG, 8, 1 << 20, 100) == \
        {"nvlink": 80, "pcie": 13, "rdma": 7}
    # distinct key components miss
    assert loaded.lookup("h800", "tree", AG, 8, 1 << 20, 100) is None
    assert loaded.lookup("h800", "ring", AR, 8, 1 << 20, 100) is None
    assert loaded.lookup("h800", "ring", AG, 4, 1 << 20, 100) is None


def test_profile_save_merges_on_disk(tmp_path):
    path = str(tmp_path / "prof.json")
    a = TuningProfile()
    a.record("h800", "ring", AG, 8, 1 << 20, 100, {"nvlink": 100})
    a.save(path)
    b = TuningProfile()
    b.record("h800", "ring", AR, 8, 1 << 20, 100, {"nvlink": 100})
    b.save(path)               # must not clobber a's entry
    merged = TuningProfile.load(path)
    assert len(merged) == 2


def test_profile_tolerates_corrupt_and_invalid_entries(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert len(TuningProfile.load(str(bad))) == 0
    # an entry whose shares don't cover the grid is unusable -> skipped
    doc = {"version": 1, "entries": [
        {"profile": "h800", "secondary_algo": "ring", "op": "all_gather",
         "n_ranks": 8, "bucket": 1 << 20, "grid": 100,
         "shares": {"nvlink": 50}},
        {"profile": "h800", "secondary_algo": "ring", "op": "all_reduce",
         "n_ranks": 8, "bucket": 1 << 20, "grid": 100,
         "shares": {"nvlink": 100}, "iterations": 3, "converged": True},
    ]}
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(doc))
    prof = TuningProfile.load(str(ok))
    assert len(prof) == 1
    assert prof.lookup("h800", "ring", AR, 8, 1 << 20, 100) == \
        {"nvlink": 100}


# ---------------------------------------------------------------------------
# warm-start round trip (acceptance: zero Stage-1 iterations + identical
# plan signatures)
# ---------------------------------------------------------------------------

def test_warm_start_zero_iterations_and_identical_signature(tmp_path):
    path = str(tmp_path / "prof.json")
    x = jnp.zeros((512, 512), jnp.float32)
    y = jnp.zeros((2048, 2048), jnp.float32)

    cold = comm_init_rank("x", 8, CommConfig(profile="h800",
                                             tuning_cache=path))
    for arr in (x, y):
        cold.plan_for(AG, arr)
        cold.plan_for(AR, arr)
    assert all(sc.tuned.iterations > 0 and not sc.warm
               for sc in cold._slots.values())
    sig_cold = cold.plan_signature()
    shares_cold = {k: dict(sc.shares) for k, sc in cold._slots.items()}
    assert cold.save_tuning() == 4

    comm_destroy_all()                 # fresh process stand-in
    warm = comm_init_rank("x", 8, CommConfig(profile="h800",
                                             tuning_cache=path))
    assert warm is not cold
    for arr in (x, y):
        warm.plan_for(AG, arr)
        warm.plan_for(AR, arr)
    assert all(sc.tuned.iterations == 0 and sc.warm and sc.tuned.converged
               for sc in warm._slots.values())
    assert {k: dict(sc.shares) for k, sc in warm._slots.items()} == \
        shares_cold
    assert warm.plan_signature() == sig_cold
    rep = warm.report()
    assert all(blk["warm"] for k, blk in rep.items()
               if isinstance(blk, dict) and "warm" in blk)


def test_warm_start_ignored_for_nccl_and_foreign_paths(tmp_path):
    path = str(tmp_path / "prof.json")
    cold = FlexCommunicator("x", 8, CommConfig(profile="h800",
                                               tuning_cache=path))
    cold.tune(AG, 256 * MiB)
    assert cold.save_tuning() == 1
    # nccl backend: single-path, never warm-started and never recorded
    nccl = FlexCommunicator("x", 8, CommConfig(backend="nccl",
                                               profile="h800",
                                               tuning_cache=path))
    assert not nccl.tune(AG, 256 * MiB).shares.get("pcie", 0)
    assert nccl.save_tuning() == 0
    # a profile written for different hardware paths must not be adopted
    prof = TuningProfile.load(path)
    prof.record("tpu_v5e", "ring", AG, 8, bucket_for(256 * MiB), SHARE_GRID,
                {"ici": 90, "weird_link": 10})
    prof.save(path)
    fresh = FlexCommunicator("y", 8, CommConfig(profile="tpu_v5e",
                                                tuning_cache=path))
    res = fresh.tune(AG, 256 * MiB)
    assert not fresh._slots[(AG, bucket_for(256 * MiB))].warm
    assert sum(res.shares.values()) == SHARE_GRID


# ---------------------------------------------------------------------------
# MeasuredTimingSource: wall-clock learning, simulator for weights only
# ---------------------------------------------------------------------------

def test_measured_source_bootstraps_from_sim_then_learns():
    model = PathTimingModel("h800")
    src = MeasuredTimingSource(model, ewma=0.5)
    bucket = 1 << 20
    fr = {"nvlink": 0.6, "pcie": 0.25, "rdma": 0.15}
    est0 = src.timings_for(AR, 8, bucket, fr, bucket=bucket)
    # bootstrap estimates ARE the simulator's weights...
    assert est0 == pytest.approx(model.measure(AR, 8, bucket, fr))
    consults = sum(s.sim_consults for s in src._slots.values())
    assert consults == 3

    # ...after which only the wall clock teaches it.  True world: nvlink
    # is 6x slower per unit share than anything the simulator believes.
    def true_step(f):
        return 1e-3 * max(f["nvlink"] * 6.0, f["pcie"], f["rdma"])

    fr2 = dict(fr, nvlink=0.59, pcie=0.26)      # one unit drained from nv
    src.ingest_step([(AR, 8, bucket, bucket, dict(fr))], true_step(fr))
    src.ingest_step([(AR, 8, bucket, bucket, dict(fr2))], true_step(fr2))
    # finite difference: (T(fr) - T(fr2)) / 0.01 = 6e-3 s per unit share
    r_obs = (true_step(fr) - true_step(fr2)) / 0.01
    assert r_obs == pytest.approx(6e-3)
    st = src._slots[(AR, bucket)]
    r_boot = est0["nvlink"] / fr["nvlink"]
    assert st.rates["nvlink"] == pytest.approx(0.5 * r_boot + 0.5 * r_obs)
    assert st.updates == 1
    # and no further simulator consultation happened
    assert sum(s.sim_consults for s in src._slots.values()) == consults


def test_measured_source_ignores_junk_steps():
    src = MeasuredTimingSource(PathTimingModel("h800"))
    src.ingest_step([], 1.0)
    src.ingest_step([(AR, 8, 1 << 20, 1 << 20, {"nvlink": 1.0})], None)
    src.ingest_step([(AR, 8, 1 << 20, 1 << 20, {"nvlink": 1.0})], -5.0)
    assert src.steps_ingested == 0


def test_probe_honors_primary_reactivation_pin():
    """A probe is not allowed to re-activate a primary the balancer has
    pinned off — it goes through LoadBalancer.move(), same rules as the
    gap rule."""
    sc = SlotController.warm_start(
        AR, 1 << 20, {"nvlink": 0, "pcie": 60, "rdma": 40}, "nvlink",
        probe_period=3)
    sc.balancer.allow_primary_reactivation = False
    flat = {"pcie": 1.0, "rdma": 1.0}
    for _ in range(30):
        sc.report(flat)
    assert sc.shares["nvlink"] == 0
    assert not sc.balancer.adjustments


def test_slot_controller_probe_rotates_and_records():
    sc = SlotController.warm_start(
        AR, 1 << 20, {"nvlink": 60, "pcie": 25, "rdma": 15}, "nvlink",
        probe_period=3)
    flat = {"nvlink": 1.0, "pcie": 1.0, "rdma": 1.0}
    moves = []
    for _ in range(30):
        adj = sc.report(flat)           # perfectly balanced: no gap moves
        if adj is not None:
            moves.append(adj)
    assert moves and all(a.kind == "probe" for a in moves)
    assert all(a.target == "nvlink" and a.moved == 1 for a in moves)
    assert {a.source for a in moves} == {"pcie", "rdma"}   # rotation
    assert sum(sc.shares.values()) == SHARE_GRID


# ---------------------------------------------------------------------------
# measured Stage 2 end to end: a StepProgram loop under forced wall-clock
# skew moves shares AGAINST the simulator's belief (acceptance criterion)
# ---------------------------------------------------------------------------

class _SkewClock:
    """Injectable StepProgram clock: each (start, stop) pair advances by a
    duration computed from the communicators' CURRENT fractions with one
    path slowed — wall-clock behavior the simulator knows nothing of."""

    def __init__(self, ctx, slow_path: str, factor: float,
                 base: float = 1e-3):
        self.ctx, self.slow, self.factor, self.base = (ctx, slow_path,
                                                       factor, base)
        self.t, self._ticks = 0.0, 0

    def __call__(self) -> float:
        self._ticks += 1
        if self._ticks % 2 == 0:
            dur = 0.0
            for comm in self.ctx.comms():
                for sc in comm._slots.values():
                    dur += max(
                        (f * (self.factor if p == self.slow else 1.0)
                         for p, f in sc.fractions().items() if f > 0),
                        default=0.0)
            self.t += self.base * max(dur, 1e-6)
        return self.t


@needs8
def test_measured_program_loop_drains_truly_slow_primary():
    """Forced skew: the wall clock says the PRIMARY is 6x slow — the
    simulator, which at this payload size believes the primary is by far
    the fastest path, would only ever move shares TOWARD it.  A measured
    StepProgram loop must drain it anyway: every such move is provably
    wall-clock-derived."""
    ctx = ParallelCtx(tp_axis="x", tp_size=8,
                      comm_config=CommConfig(profile="h800",
                                             timing="measured"))
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("x",))

    def builder():
        return jax.jit(shard_map(lambda v: ctx.tp_all_reduce(v), mesh=mesh,
                                 in_specs=(P("x"),), out_specs=P("x"),
                                 check_vma=False))

    clock = _SkewClock(ctx, slow_path="nvlink", factor=6.0)
    prog = StepProgram(builder, ctx, clock=clock, name="measured-e2e")
    x = jnp.arange(8 * 512 * 8, dtype=jnp.float32).reshape(8 * 512, 8)
    assert ctx.timing_kind() == "measured"
    try:
        prog.step(x)                        # trace + Stage-1 tune
        comm = ctx.comms()[0]
        (sc,) = comm._slots.values()
        # small-bucket Stage 1 keeps everything on the primary; force a
        # multi-path split (fast window/period so the short loop reacts)
        sc.balancer = LoadBalancer({"nvlink": 60, "pcie": 25, "rdma": 15},
                                   "nvlink", window=3, invoke_period=3)
        sc.probe_period = 5
        for _ in range(45):
            prog.step(x)
        assert comm.timing.steps_ingested >= 40
        drains = [a for a in sc.balancer.adjustments
                  if a.source == "nvlink" and a.kind == "balance"]
        assert drains, "no measured-feedback move drained the primary"
        assert sc.shares["nvlink"] < 60
        assert sum(sc.shares.values()) == SHARE_GRID
        # the simulator was consulted exactly once per path — for the
        # bootstrap apportionment weights — and never for a timing
        rep = comm.timing.report()
        (slot_rep,) = rep["slots"].values()
        assert slot_rep["sim_consults"] == 3
        assert slot_rep["updates"] > 0
        assert comm.report()["timing_source"] == "measured"
    finally:
        prog.close()


# ---------------------------------------------------------------------------
# quantization-aware probing: probes snap to the RoutePlan grain
# ---------------------------------------------------------------------------

def _chunk_quantizer(order=("nvlink", "pcie", "rdma"), grid=16):
    """Stand-in plan quantizer: the same largest-remainder chunk mapping
    the data plane applies (collectives.quantize_shares) keyed by link
    name directly."""
    from repro.core.collectives import quantize_shares

    def q(shares):
        return tuple(sorted(quantize_shares(shares, order, grid).items()))
    return q


def test_probe_promoted_to_one_grain_step():
    """A 1-unit probe from {60, 25, 15} does NOT change the 16-chunk
    quantization — the slot must promote the probe to the smallest move
    that flips the executed plan instead of burning a no-op adjustment."""
    sc = SlotController.warm_start(
        AR, 1 << 20, {"nvlink": 60, "pcie": 25, "rdma": 15}, "nvlink",
        probe_period=3, plan_quantizer=_chunk_quantizer())
    q = _chunk_quantizer()
    flat = {"nvlink": 1.0, "pcie": 1.0, "rdma": 1.0}
    base = q(sc.shares)
    adj = None
    for _ in range(10):
        adj = sc.report(flat)
        if adj is not None:
            break
    assert adj is not None and adj.kind == "probe"
    assert adj.moved > 1                      # promoted past the 1-unit move
    assert q(sc.shares) != base               # the executed plan changed
    assert sum(sc.shares.values()) == SHARE_GRID


def test_sub_grain_probe_is_skipped():
    """When even draining a secondary entirely cannot flip the quantized
    plan, the probe is skipped — no adjustment is recorded at all."""
    shares = {"nvlink": 97, "pcie": 2, "rdma": 1}
    sc = SlotController.warm_start(
        AR, 1 << 20, shares, "nvlink",
        probe_period=3, plan_quantizer=_chunk_quantizer())
    q = _chunk_quantizer()
    # precondition: no k-unit drain of either secondary flips the plan
    for src in ("pcie", "rdma"):
        for k in range(1, shares[src] + 1):
            cand = dict(shares)
            cand[src] -= k
            cand["nvlink"] += k
            assert q(cand) == q(shares)
    flat = {"nvlink": 1.0, "pcie": 1.0, "rdma": 1.0}
    for _ in range(30):
        sc.report(flat)
    assert not sc.balancer.adjustments
    assert sc.shares == shares


def test_communicator_probes_move_the_executed_plan():
    """End to end through the communicator: measured-mode probes on a
    live slot always land on a different quantized plan (the PlanCache
    registers a retrace), never a rounding no-op."""
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800",
                                               timing="measured",
                                               tag="quantprobe"))
    sc = comm.slot(AR, 1 << 20)
    sc.balancer = LoadBalancer({"nvlink": 60, "pcie": 25, "rdma": 15},
                               "nvlink")
    sc.probe_period = 3
    before = comm._plan_units(AR, sc.shares)
    flat = {"nvlink": 1.0, "pcie": 1.0, "rdma": 1.0}
    adj = None
    for _ in range(10):
        adj = sc.report(flat)
        if adj is not None:
            break
    assert adj is not None and adj.kind == "probe"
    assert comm._plan_units(AR, sc.shares) != before


# ---------------------------------------------------------------------------
# per-tier rollup (DESIGN.md §9 reporting satellite)
# ---------------------------------------------------------------------------

def test_slot_rollup_groups_by_tier_and_describe_names_it():
    intra = SlotController.warm_start(
        AR, 1 << 20, {"nvlink": 70, "pcie": 20, "rdma": 10}, "nvlink")
    inter = SlotController.warm_start(
        AR, 1 << 20, {"rail": 80, "xrail": 15, "host_tcp": 5}, "rail",
        tier="inter")
    inter.balancer.move("xrail", "rail", 1)
    inter.balancer.move("host_tcp", "rail", 1, kind="probe")
    roll = SlotController.rollup([intra, inter, inter])
    assert set(roll) == {"intra", "inter"}
    assert roll["intra"] == {"slots": 1, "warm": 1, "converged": 1,
                             "stage2_adjustments": 0, "probes": 0,
                             "member_moves": 0, "drained_members": 0,
                             "compressed_slots": 0}
    assert roll["inter"]["slots"] == 2
    assert roll["inter"]["stage2_adjustments"] == 4   # 2 each, counted twice
    assert roll["inter"]["probes"] == 2
    model = PathTimingModel("h800")
    blk = intra.describe(model, 8)
    assert blk["tier"] == "intra"
    assert blk["evaluator"] == {"window": 10, "samples": 0}
