"""StepProgram runtime tests: the plan-keyed executable cache, per-program
Stage-2 replay recorders, and the acceptance behaviour of DESIGN.md §7 —
an oscillation A→B→A performs exactly 2 traces (2 rebuilds + a hit) while
the plan cache records the return to A as hit+retrace, and interleaved
programs on one memoized communicator keep disjoint replay logs without
``CommConfig.tag``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core.communicator import (CommConfig, FlexCommunicator,
                                     bucket_for, comm_destroy_all,
                                     comm_init_rank)
from repro.core.routing import PlanCache
from repro.core.topology import Collective
from repro.models.tp import ParallelCtx, single_device_ctx
from repro.runtime.exec_cache import ExecutableCache
from repro.runtime.program import StepProgram, program_scope

needs8 = pytest.mark.skipif(len(jax.devices()) < 8,
                            reason="needs 8 CPU devices")


@pytest.fixture(autouse=True)
def _fresh_comms():
    comm_destroy_all()
    yield
    comm_destroy_all()


def _mesh1d():
    return Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("x",))


def _tp_ctx():
    return ParallelCtx(tp_axis="x", tp_size=8,
                       comm_config=CommConfig(profile="h800"))


def _flip_shares(comm: FlexCommunicator, delta: int) -> None:
    """Move ``delta`` grid units between primary and the first secondary on
    every tuned balancer — a deterministic stand-in for a Stage-2 move big
    enough to change the quantized split (grid 100 → 16 chunk units)."""
    for bal in comm._balancers.values():
        sec = next(p for p in bal.shares if p != bal.primary)
        bal.shares[bal.primary] -= delta
        bal.shares[sec] += delta
        assert all(s >= 0 for s in bal.shares.values())


# ---------------------------------------------------------------------------
# ExecutableCache
# ---------------------------------------------------------------------------

def test_exec_cache_hit_rebuild_evict_counters():
    cache = ExecutableCache(capacity=2)
    assert cache.lookup("a", lambda: "exe-a") == "exe-a"
    assert cache.lookup("a", lambda: "never") == "exe-a"
    assert cache.stats.hits == 1 and cache.stats.rebuilds == 1
    cache.lookup("b", lambda: "exe-b")
    cache.lookup("c", lambda: "exe-c")        # evicts LRU entry "a"
    assert cache.stats.evictions == 1
    assert "a" not in cache and "b" in cache and "c" in cache
    rep = cache.report()
    assert rep == {"hits": 1, "rebuilds": 3, "evictions": 1, "size": 2,
                   "capacity": 2}


def test_exec_cache_lru_refresh_on_hit():
    cache = ExecutableCache(capacity=2)
    cache.lookup("a", lambda: 1)
    cache.lookup("b", lambda: 2)
    cache.get("a")                             # refresh "a" to MRU
    cache.lookup("c", lambda: 3)               # evicts "b", not "a"
    assert "a" in cache and "b" not in cache


def test_exec_cache_rejects_zero_capacity():
    with pytest.raises(ValueError):
        ExecutableCache(capacity=0)


# ---------------------------------------------------------------------------
# plan signatures
# ---------------------------------------------------------------------------

def test_plan_cache_signature_snapshots_slots():
    import repro.core.routing as rt
    cache = PlanCache()
    assert cache.plan_signature() == ()
    p = cache.lookup(Collective.ALL_REDUCE, 1 << 20,
                     lambda: rt.build_plan(Collective.ALL_REDUCE, "x",
                                           {"primary": 80, "staged": 20}))
    sig = cache.plan_signature()
    assert sig == (("all_reduce", 1 << 20, p),)
    assert cache.plan_signature() == sig       # stable without a move


def test_communicator_signature_tracks_share_moves():
    comm = FlexCommunicator("x", 8, CommConfig(profile="h800"))
    x = jnp.zeros((512, 512), jnp.float32)
    comm.plan_for(Collective.ALL_REDUCE, x)
    sig_a = comm.plan_signature()
    assert comm.plan_signature() == sig_a      # refresh is idempotent
    _flip_shares(comm, 20)                     # A -> B
    sig_b = comm.plan_signature()
    assert sig_b != sig_a
    _flip_shares(comm, -20)                    # move back
    retraces_before = comm.plan_cache.stats.retraces
    hits_before = comm.plan_cache.stats.hits
    assert comm.plan_signature() == sig_a
    # the return to a previously-seen plan is recorded as hit AND retrace
    assert comm.plan_cache.stats.retraces == retraces_before + 1
    assert comm.plan_cache.stats.hits > hits_before


# ---------------------------------------------------------------------------
# frozen CommConfig (satellite: the comm_init_rank memo key must not be
# mutable after construction)
# ---------------------------------------------------------------------------

def test_commconfig_is_frozen():
    cfg = CommConfig()
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.backend = "nccl"
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.tag = "oops"
    # replacement (not mutation) is the supported way to derive configs
    cfg2 = dataclasses.replace(cfg, backend="nccl")
    assert cfg2.backend == "nccl" and cfg.backend == "flexlink"


# ---------------------------------------------------------------------------
# per-program replay recorders (regression for the old KNOWN LIMIT: one
# shared per-communicator log, overwritten on interleaved traces)
# ---------------------------------------------------------------------------

def test_interleaved_recorders_keep_disjoint_multisets():
    comm = comm_init_rank("x", 8, CommConfig(profile="h800"))
    ra = comm.register_recorder("train")
    rb = comm.register_recorder("decode")
    x = jnp.zeros((512, 512), jnp.float32)
    y = jnp.zeros((256, 256), jnp.float32)

    def trace_train():                         # 3 identical + 1 distinct
        with comm.recording(ra):
            for _ in range(3):
                comm.plan_for(Collective.ALL_REDUCE, x)
            comm.plan_for(Collective.ALL_GATHER, x)

    def trace_decode():                        # 2 calls, smaller payload
        with comm.recording(rb):
            for _ in range(2):
                comm.plan_for(Collective.ALL_REDUCE, y)

    trace_train()
    trace_decode()                             # interleaved with train
    comm.observe_executed_step(ra)
    comm.observe_executed_step(rb)
    assert len(ra.issued_calls()) == 4         # multiplicity kept
    assert len(rb.issued_calls()) == 2         # NOT overwritten by train
    nb_a = {n for _, n, _w in ra.issued_calls()}
    nb_b = {n for _, n, _w in rb.issued_calls()}
    assert nb_a.isdisjoint(nb_b)               # disjoint logs, same comm
    assert comm.issued_calls() == []           # default recorder untouched
    trace_train()                              # Stage-2 re-trace of train
    comm.observe_executed_step(ra)
    assert len(ra.issued_calls()) == 4         # replaced, not appended
    assert len(rb.issued_calls()) == 2         # decode log untouched
    # steps without a re-trace keep replaying the promoted log
    comm.observe_executed_step(ra)
    assert len(ra.issued_calls()) == 4


def test_register_recorder_idempotent_and_unregister():
    comm = comm_init_rank("x", 8, CommConfig(profile="h800"))
    ra = comm.register_recorder("p")
    assert comm.register_recorder("p") is ra
    assert comm.recorder("p") is ra
    comm.unregister_recorder("p")
    with pytest.raises(KeyError):
        comm.recorder("p")
    comm.unregister_recorder("p")              # idempotent


def test_reset_issued_clears_program_recorders_too():
    comm = comm_init_rank("x", 8, CommConfig(profile="h800"))
    rec = comm.register_recorder("p")
    x = jnp.zeros((512, 512), jnp.float32)
    with comm.recording(rec):
        comm.plan_for(Collective.ALL_REDUCE, x)
    comm.plan_for(Collective.ALL_REDUCE, x)    # default recorder
    assert rec.issued_calls() and comm.issued_calls()
    comm.reset_issued()
    assert not rec.issued_calls() and not comm.issued_calls()


# ---------------------------------------------------------------------------
# StepProgram end-to-end (the acceptance scenario)
# ---------------------------------------------------------------------------

def _all_reduce_program(ctx, mesh, *, n_calls=1, rows=512, capacity=8,
                        name=""):
    """A tiny sharded step issuing ``n_calls`` tp all_reduces per trace,
    with a trace counter so re-jits are observable."""
    traces = []

    def builder():
        def step(v):
            traces.append(1)
            out = v
            for _ in range(n_calls):
                out = ctx.tp_all_reduce(out)
            return out
        return jax.jit(shard_map(step, mesh=mesh, in_specs=(P("x"),),
                                 out_specs=P("x"), check_vma=False))

    prog = StepProgram(builder, ctx, capacity=capacity, name=name)
    x = jnp.arange(8 * rows * 8, dtype=jnp.float32).reshape(8 * rows, 8)
    return prog, x, traces


@needs8
def test_oscillation_two_traces_one_hit():
    """A→B→A: exactly 2 traces; exec cache shows 2 rebuilds + >=1 hit; the
    plan cache still records the return to A as hit+retrace."""
    ctx = _tp_ctx()
    prog, x, traces = _all_reduce_program(ctx, _mesh1d())
    ref = np.asarray(x).reshape(8, -1, 8).sum(0)

    out = prog.step(x)                         # trace A
    np.testing.assert_allclose(np.asarray(out)[:x.shape[0] // 8], ref,
                               rtol=1e-5)
    comm = ctx.comms()[0]
    _flip_shares(comm, 20)                     # A -> B
    prog.step(x)                               # trace B
    assert len(traces) == 2
    retr_before = comm.plan_cache.stats.retraces
    hits_before = comm.plan_cache.stats.hits
    _flip_shares(comm, -20)                    # B -> back to A
    out = prog.step(x)                         # executable-cache hit
    np.testing.assert_allclose(np.asarray(out)[:x.shape[0] // 8], ref,
                               rtol=1e-5)
    assert len(traces) == 2                    # NO third trace
    rep = prog.cache.report()
    assert rep["rebuilds"] == 2 and rep["hits"] >= 1
    assert rep["evictions"] == 0
    # the plan cache recorded the oscillation back as hit+retrace
    assert comm.plan_cache.stats.retraces == retr_before + 1
    assert comm.plan_cache.stats.hits > hits_before


@needs8
def test_capacity_one_forces_rejit_on_each_flip():
    ctx = _tp_ctx()
    prog, x, traces = _all_reduce_program(ctx, _mesh1d(), capacity=1)
    prog.step(x)
    comm = ctx.comms()[0]
    _flip_shares(comm, 20)
    prog.step(x)
    _flip_shares(comm, -20)
    prog.step(x)                               # A evicted -> re-trace
    assert len(traces) == 3
    rep = prog.cache.report()
    assert rep["rebuilds"] == 3 and rep["evictions"] == 2


@needs8
def test_interleaved_programs_disjoint_replay_no_tag():
    """Two concurrently ticking programs on ONE axis and ONE CommConfig
    (no tag) keep isolated replay multisets with correct per-step
    multiplicity — the acceptance regression for the old shared log."""
    ctx = _tp_ctx()
    mesh = _mesh1d()
    prog_a, xa, _ = _all_reduce_program(ctx, mesh, n_calls=3, rows=512,
                                        name="train-like")
    prog_b, xb, _ = _all_reduce_program(ctx, mesh, n_calls=1, rows=256,
                                        name="decode-like")
    comm = ctx.comms()[0]
    assert len(ctx.comms()) == 1               # genuinely shared
    # interleave the two programs' ticks
    for _ in range(2):
        prog_a.step(xa)
        prog_b.step(xb)
    ra = comm.recorder(prog_a.name).issued_calls()
    rb = comm.recorder(prog_b.name).issued_calls()
    assert len(ra) == 3 and len(rb) == 1       # per-step multiplicity
    assert {n for _, n, _w in ra}.isdisjoint({n for _, n, _w in rb})
    # both programs report through the shared comm's report
    progs = comm.report()["programs"]
    assert progs[prog_a.name]["replay_len"] == 3
    assert progs[prog_b.name]["replay_len"] == 1


@needs8
def test_sibling_program_slots_do_not_rekey():
    """A program's executable-cache signature covers only the slots ITS
    traces touch: a sibling program tuning a new bucket — or oscillating a
    slot the first program never uses — on the SAME communicator must not
    force a spurious re-jit."""
    ctx = _tp_ctx()
    mesh = _mesh1d()
    prog_a, xa, traces_a = _all_reduce_program(ctx, mesh, rows=512,
                                               name="small-bucket")
    # rows chosen so the per-shard payload lands in a DIFFERENT bucket
    prog_b, xb, traces_b = _all_reduce_program(ctx, mesh, rows=49152,
                                               name="big-bucket")
    assert bucket_for(512 * 8 * 4) != bucket_for(49152 * 8 * 4)
    prog_a.step(xa)
    assert prog_a.cache.report()["rebuilds"] == 1
    prog_b.step(xb)                  # tunes a NEW slot on the shared comm
    comm = ctx.comms()[0]
    assert len(comm._balancers) == 2
    prog_a.step(xa)                  # foreign slot must not re-key a
    rep_a = prog_a.cache.report()
    assert rep_a["rebuilds"] == 1 and rep_a["hits"] == 1
    assert len(traces_a) == 1
    # oscillate ONLY b's slot: a stays cached, b re-keys
    bal = comm._balancers[(Collective.ALL_REDUCE, bucket_for(49152 * 8 * 4))]
    sec = next(p for p in bal.shares if p != bal.primary)
    assert bal.shares[bal.primary] >= 20
    bal.shares[bal.primary] -= 20
    bal.shares[sec] += 20
    prog_b.step(xb)
    prog_a.step(xa)
    assert len(traces_a) == 1
    assert prog_a.cache.report()["rebuilds"] == 1
    assert prog_b.cache.report()["rebuilds"] == 2 and len(traces_b) == 2


@needs8
def test_lower_does_not_pollute_replay_log():
    """Dry-run lowering traces the step but never executes it, so it must
    not leave pending calls that a later live execution would replay into
    Stage 2 (doubling the observed multiset)."""
    ctx = _tp_ctx()
    prog, x, traces = _all_reduce_program(ctx, _mesh1d())
    lowered = prog.lower(jax.ShapeDtypeStruct(x.shape, x.dtype))
    assert lowered is not None and len(traces) == 1
    comm = ctx.comms()[0]
    assert comm.recorder(prog.name).issued_calls() == []
    assert comm.issued_calls() == []           # default untouched too
    prog.step(x)                               # live trace + observe
    assert len(comm.recorder(prog.name).issued_calls()) == 1  # not 2
    # the scratch lower-recorder was unregistered again
    assert set(comm.report()["programs"]) == {prog.name}


@needs8
def test_program_scope_unregisters_on_exit():
    ctx = _tp_ctx()
    mesh = _mesh1d()

    def builder():
        return jax.jit(shard_map(lambda v: ctx.tp_all_reduce(v), mesh=mesh,
                                 in_specs=(P("x"),), out_specs=P("x"),
                                 check_vma=False))

    with program_scope(builder, ctx) as prog:
        prog(jnp.zeros((8 * 64, 8), jnp.float32))
        name = prog.name
        assert comm_init_rank("x", 8, CommConfig(profile="h800")) \
            .recorder(name) is not None
    with pytest.raises(KeyError):
        ctx.comms()[0].recorder(name)


# ---------------------------------------------------------------------------
# host loops through the runtime
# ---------------------------------------------------------------------------

def test_run_loop_drives_program_and_legacy_builder():
    from repro.train.loop import LoopConfig, run_loop
    ctx = single_device_ctx()

    def make_batches():
        while True:
            yield {}

    def builder():
        def step(params, opt_state, batch):
            return (params, opt_state,
                    {"loss": jnp.float32(1.0), "grad_norm": jnp.float32(0.0),
                     "lr": jnp.float32(1e-3)})
        return step

    loop = LoopConfig(total_steps=3, log_every=0)
    # legacy path: a zero-arg builder gets wrapped into a StepProgram
    _, _, hist = run_loop(builder, {}, {}, make_batches(), ctx, loop,
                          log=lambda s: None)
    assert hist == [1.0, 1.0, 1.0]
    # program path
    prog = StepProgram(builder, ctx)
    _, _, hist = run_loop(prog, {}, {}, make_batches(), ctx, loop,
                          log=lambda s: None)
    assert hist == [1.0, 1.0, 1.0]
    # a commless ctx has a constant signature: exactly one build ever
    assert prog.cache.report()["rebuilds"] == 1
    assert prog.cache.report()["hits"] == 2


def test_serve_engine_reports_executable_cache_stats():
    from repro.configs import get_config
    from repro.models.transformer import init_params
    from repro.serving.engine import ServeConfig, ServeEngine
    cfg = get_config("glm4-9b").reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(params, cfg, single_device_ctx(),
                      ServeConfig(slots=2, cache_len=48))
    eng.submit([5, 6, 7], max_new=4)
    eng.submit([9, 10, 11], max_new=4)
    eng.run_until_drained()
    assert len(eng.finished()) == 2
    rep = eng.comm_report()
    ec = rep["executable_cache"]
    assert ec["rebuilds"] == 1                 # single-device: one trace
    assert ec["hits"] >= 1                     # every later tick is a hit
    assert ec["evictions"] == 0
