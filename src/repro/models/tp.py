"""Parallelism context — how model code reaches the FlexLink backend.

Model layers never call ``jax.lax`` collectives directly; they go through a
``ParallelCtx`` that (a) no-ops when the axis is absent/size-1 (single-device
smoke tests), and (b) routes every bandwidth-bound collective through the
FlexCommunicator so the paper's multi-path aggregation is the framework's
communication backend, not a bolt-on.

The ctx is constructed once per launch (train.py / serve.py / dryrun.py)
from the mesh + CommConfig and closed over by the jitted step function.
Communicators come from the memoized ``comm_init_rank`` registry, so
rebuilding a ctx (new launcher, re-jitted step) reuses the axis' Stage-1
tuning and keeps one Stage-2 balancer per (axis, config) — every step
function on an axis sees the same RoutePlan engine.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size

from repro.core.communicator import (CommConfig, FlexCommunicator,
                                     comm_init_rank)


def _axis_in_scope(name: Optional[str]) -> bool:
    if name is None:
        return False
    try:
        axis_size(name)
        return True
    except NameError:
        return False


@dataclasses.dataclass
class ParallelCtx:
    """Axis names + communicators for one step function.

    tp_axis    : tensor-parallel axis ("model"); None disables TP collectives
    dp_axis    : data-parallel axis ("data")
    node_axis  : inter-node axis ("node") — crosses the cluster's NIC tier;
                 gradient reduction becomes the two-tier hierarchical
                 AllReduce of ``repro.cluster`` (DESIGN.md §9)
    pod_axis   : pod axis for multi-pod meshes.  On a cluster mesh (node
                 axis live) with a pod-tier topology this axis crosses
                 the pod/DCN tier as its own FlexCommunicator and joins
                 the hierarchical compositions + the expert-parallel
                 span (DESIGN.md §15); on the legacy pod-only production
                 mesh it stays a plain psum (gradient reduction only)
    tp/dp size : static sizes (mesh-derived; needed before tracing)
    cluster    : the ClusterTopology behind the node axis; synthesized
                 from the comm profile (cluster_for) when left None
    """

    tp_axis: Optional[str] = None
    dp_axis: Optional[str] = None
    node_axis: Optional[str] = None
    pod_axis: Optional[str] = None
    tp_size: int = 1
    dp_size: int = 1
    node_size: int = 1
    pod_size: int = 1
    comm_config: CommConfig = dataclasses.field(default_factory=CommConfig)
    cluster: Optional[object] = None      # ClusterTopology
    #: the FabricClock driving live health transitions (repro.faults,
    #: DESIGN.md §14) — set by ``FabricClock.attach``; None on the
    #: fault-free (byte-identical) path.
    fault_clock: Optional[object] = None
    _tp_comm: Optional[FlexCommunicator] = None
    _dp_comm: Optional[FlexCommunicator] = None
    _node_comm: Optional[FlexCommunicator] = None
    _pod_comm: Optional[FlexCommunicator] = None
    _cluster_comm: Optional[object] = None  # ClusterCommunicator

    def __post_init__(self):
        if self.tp_axis and self.tp_size > 1:
            self._tp_comm = comm_init_rank(
                self.tp_axis, self.tp_size, self.comm_config,
                ortho_name=self.dp_axis if self.dp_size > 1 else None)
        if self.dp_axis and self.dp_size > 1:
            self._dp_comm = comm_init_rank(
                self.dp_axis, self.dp_size, self.comm_config,
                ortho_name=self.tp_axis if self.tp_size > 1 else None)
        if self.node_axis and self.node_size > 1:
            # deferred import: the cluster package rides on top of the
            # communicator stack this module fronts
            from repro.cluster.communicator import ClusterCommunicator
            from repro.cluster.topology import cluster_for
            want_pods = (self.pod_size
                         if self.pod_axis and self.pod_size > 1 else 1)
            if self.cluster is None:
                self.cluster = cluster_for(self.comm_config.profile,
                                           self.node_size, pods=want_pods)
            if self.cluster.n_nodes != self.node_size:
                raise ValueError(
                    f"cluster {self.cluster.name!r} has "
                    f"{self.cluster.n_nodes} nodes but the mesh's node "
                    f"axis spans {self.node_size}")
            if self.cluster.node.name != self.comm_config.profile:
                raise ValueError(
                    f"cluster {self.cluster.name!r} is built from "
                    f"{self.cluster.node.name!r} nodes but the comm "
                    f"profile is {self.comm_config.profile!r} — reports, "
                    f"timing constants and warm-start keys would describe "
                    f"a fabric that never ran")
            # the NIC tier is its own communicator: same CommConfig knobs,
            # the tier profile's link pool — its SlotControllers balance
            # the inter tier independently of the intra fabric
            inter_cfg = dataclasses.replace(
                self.comm_config, profile=self.cluster.nic_tier.name)
            ortho = (self.dp_axis if self.dp_size > 1
                     else (self.tp_axis if self.tp_size > 1 else None))
            self._node_comm = comm_init_rank(
                self.node_axis, self.node_size, inter_cfg,
                ortho_name=ortho)
            if self.cluster.n_pods > 1 and self.cluster.n_pods != want_pods:
                raise ValueError(
                    f"cluster {self.cluster.name!r} has "
                    f"{self.cluster.n_pods} pods but the mesh's pod axis "
                    f"spans {want_pods}")
            if want_pods > 1 and self.cluster.n_pods == want_pods:
                # the pod/DCN tier is its own communicator too — same
                # CommConfig knobs against the spine link pool, so the
                # pod tier tunes, drains, compresses and rekeys exactly
                # like the tiers below it (DESIGN.md §15)
                pod_cfg = dataclasses.replace(
                    self.comm_config, profile=self.cluster.pod_tier.name)
                self._pod_comm = comm_init_rank(
                    self.pod_axis, self.pod_size, pod_cfg,
                    ortho_name=self.node_axis)
            self._cluster_comm = ClusterCommunicator(
                self.cluster, self._dp_comm, self._node_comm,
                self._pod_comm)

    # -- plan-engine plumbing -------------------------------------------------

    def comms(self) -> Tuple[FlexCommunicator, ...]:
        """The live communicators behind this ctx (tp, dp, then the
        cluster's NIC tier, then its pod tier)."""
        return tuple(c for c in (self._tp_comm, self._dp_comm,
                                 self._node_comm, self._pod_comm)
                     if c is not None)

    def observe_executed_step(self) -> bool:
        """Host-side Stage-2 hook over every communicator's DEFAULT
        recorder (direct, program-less use of the data plane).

        Returns True when any balancer moved a share — the caller should
        rebuild/re-trace its jitted step so the new RoutePlans take effect
        (the plan cache records the event as a re-trace).  A fresh trace
        REPLACES the replay log rather than appending to it, so re-traces
        don't double-count and no reset is needed between rebuilds.
        StepProgram-driven loops use :meth:`observe_program` instead, which
        replays one program's isolated recorder.
        """
        changed = False
        for comm in self.comms():
            changed |= comm.observe_executed_step()
        return changed

    # -- StepProgram registration (runtime/program.py, DESIGN.md §7) ----------

    def register_program(self, name: str) -> str:
        """Register one per-program ReplayRecorder with every communicator
        (idempotent — memoized comms keep a re-registered program's log)."""
        for comm in self.comms():
            comm.register_recorder(name)
        return name

    def unregister_program(self, name: str) -> None:
        for comm in self.comms():
            comm.unregister_recorder(name)

    @contextlib.contextmanager
    def recording(self, name: str):
        """Scope every collective traced inside to ``name``'s recorders —
        a StepProgram wraps each executable call (and dry-run lowering) in
        this so interleaved programs keep disjoint replay logs."""
        with contextlib.ExitStack() as stack:
            for comm in self.comms():
                stack.enter_context(comm.recording(comm.recorder(name),
                                                   name=name))
            yield

    # -- issue/await overlap scopes (DESIGN.md §11) ----------------------------

    @contextlib.contextmanager
    def issue(self, tag: str):
        """Mark the collectives traced inside as ONE in-flight plan.

        Their replay records land in the active program's ``name/tag``
        sub-recorder (disjoint Stage-2 multisets per bucket) and join the
        open issue window on every communicator; all plans issued before
        the next :meth:`await_all` share the window, and each call's
        Stage-2 timings are priced at the window's population — the
        contention model of ``PathTimingModel``.  A ctx with no live
        communicators no-ops."""
        with contextlib.ExitStack() as stack:
            for comm in self.comms():
                stack.enter_context(comm.issue_scope(tag))
            yield

    def await_all(self, tree=None):
        """Barrier for every issued plan: closes the communicators' open
        issue windows (plans issued later no longer contend with these)
        and pins ``tree`` behind an optimization barrier so XLA cannot
        sink consumers (the optimizer) above the in-flight transfers.
        Returns ``tree`` (barriered), or None when none is given."""
        for comm in self.comms():
            comm.await_barrier()
        if tree is None:
            return None
        return lax.optimization_barrier(tree)

    def observe_program(self, name: str,
                        elapsed_s: Optional[float] = None) -> bool:
        """Stage-2 feedback from ONE program's replay logs — its base
        recorder plus every issue sub-recorder its traces registered
        (``name/tag`` per in-flight bucket); True when any share moved
        (the program's next signature lookup re-keys).

        ``elapsed_s`` is the executed step's measured wall-clock duration
        (StepProgram measured mode).  Each communicator apportions it over
        its OWN replay multiset — the balancer only compares relative
        per-path times, so the tp and dp axes sharing one step's duration
        does not bias either loop."""
        changed = False
        for comm in self.comms():
            changed |= comm.observe_recorders(comm.family_recorders(name),
                                              elapsed_s=elapsed_s)
        return changed

    def ef_codec_name(self, payload_dtype: str = "float32") -> str:
        """The wire codec the comm config enables that loses bits for
        ``payload_dtype`` gradient payloads ("" when compression is off or
        bit-exact for that dtype) — the tree-level error-feedback gate for
        bucketed gradient sync (train/bucketer.py, DESIGN.md §12).  This
        decides whether the residual STATE exists; whether each bucket's
        roundtrip actually runs is gated per slot by
        :meth:`ef_active_for`."""
        from repro.core.codecs import lossy_codec_name
        return lossy_codec_name(self.comm_config.compress, payload_dtype)

    def ef_active_for(self, nbytes: int, dtype, expert: bool = False) -> bool:
        """Does the reduce of one gradient bucket actually traverse a wire
        codec that loses bits for ``dtype``?  Queries the codec choice of
        every slot the bucket's reduce crosses — the per-bucket error-
        feedback gate (train/bucketer.py): a slot whose tuner declined
        compression ships exact bytes, and perturbing it with a residual
        for a quantization that never happens would be pure noise."""
        from repro.core.codecs import get_codec
        from repro.core.communicator import bucket_for
        from repro.core.topology import Collective

        legs = []   # (communicator, collective, payload bytes) traversed
        if expert:
            # ep_a2a expert grads are pre-accumulated by the backward
            # all_to_all over every ep tier (data + node + pod when
            # live); the only remaining reduce is a plain psum over
            # whatever gradient axis the ep span excludes — no wire
            # codec ever touches them, so EF stays off.  The historical
            # node-tier AR leg existed only while experts were sharded
            # over the data axis alone.
            pass
        elif self._cluster_comm is not None:
            cc = self._cluster_comm
            if cc.hierarchical:
                tiers = cc.comms()
                nb = nbytes
                for t in tiers[:-1]:
                    legs.append((t, Collective.REDUCE_SCATTER, nb))
                    nb = max(nb // t.n_ranks, 1)
                legs.append((tiers[-1], Collective.ALL_REDUCE, nb))
                for t in reversed(tiers[:-1]):
                    legs.append((t, Collective.ALL_GATHER, nb))
                    nb *= t.n_ranks
            else:
                legs = [(c, Collective.ALL_REDUCE, nbytes)
                        for c in cc.comms()]
        elif self._dp_comm is not None:
            legs.append((self._dp_comm, Collective.ALL_REDUCE, nbytes))
        for comm, op, n in legs:
            for codec in comm.slot(op, bucket_for(n)).codecs.values():
                if not get_codec(codec).lossless_for(dtype):
                    return True
        return False

    def timing_kind(self) -> str:
        """The active TimingSource kind: "measured" if ANY communicator
        balances on wall-clock observation, else "sim" ("none" without
        live communicators — single-device ctx)."""
        kinds = {c.timing.kind for c in self.comms()}
        if "measured" in kinds:
            return "measured"
        return "sim" if kinds else "none"

    # -- TuningProfile warm-start plumbing (control/profile.py) ---------------

    def save_tuning_profile(self, path: Optional[str] = None) -> int:
        """Persist every communicator's converged Stage-1 shares to the
        warm-start cache (``path`` overrides each config's
        ``tuning_cache``).  Returns total entries recorded."""
        return sum(c.save_tuning(path) for c in self.comms())

    def tuning_status(self) -> Dict[str, Dict[str, object]]:
        """Warm/cold Stage-1 provenance per axis per slot (dry-run and
        loop reporting)."""
        return {c.axis_name: c.tuning_status() for c in self.comms()}

    def plan_signature(self, program: Optional[str] = None) -> Tuple:
        """Frozen tuple of the communicators' current quantized plans —
        the StepProgram executable-cache key.  With ``program`` set, each
        communicator's half is restricted to the slots that program's
        traces actually touched (its recorder footprint), so sibling
        programs on shared communicators don't re-key each other.
        Refreshing resolves each slot through the plan cache (hit/retrace
        stats)."""
        sigs = []
        for c in self.comms():
            touched = c.family_footprint(program) if program else None
            sigs.append((c.axis_name, c.plan_signature(touched)))
        return tuple(sigs)

    def reset_issued(self) -> None:
        """Clear every communicator's issued-call replay log.  Only for
        explicit isolation (e.g. tests, or retiring a workload): the log is
        shared by every ctx on the same memoized communicator, so clearing
        it mid-run would silence Stage-2 for sibling step functions."""
        for comm in self.comms():
            comm.reset_issued()

    def comm_report(self) -> Dict[str, object]:
        """Tuning + plan-cache stats keyed by mesh axis; a hierarchical
        ctx adds the cluster's topology + per-tier rollup (the tier
        communicators' full reports already sit under their axis keys)."""
        out: Dict[str, object] = {c.axis_name: c.report()
                                  for c in self.comms()}
        if self._cluster_comm is not None:
            out["cluster"] = self._cluster_comm.summary()
        if self.fault_clock is not None:
            out["faults"] = self.fault_clock.report()
        return out

    def apply_health_state(self, degrades) -> Dict[str, object]:
        """Broadcast one committed fabric state to every live
        communicator (FabricClock's commit hook); returns the per-axis
        transition records of the ones that actually changed."""
        out: Dict[str, object] = {}
        for comm in self.comms():
            info = comm.apply_health_state(degrades)
            if info:
                out[comm.axis_name] = info
        return out

    # -- tensor-parallel collectives (FlexLink-backed) -----------------------

    def tp_all_reduce(self, x: jax.Array) -> jax.Array:
        if self._tp_comm is None:
            return x
        return self._tp_comm.all_reduce(x)

    def tp_all_gather(self, x: jax.Array, tiled: bool = True) -> jax.Array:
        if self._tp_comm is None:
            return x
        return self._tp_comm.all_gather(x, tiled=tiled)

    def tp_reduce_scatter(self, x: jax.Array) -> jax.Array:
        if self._tp_comm is None:
            return x
        return self._tp_comm.reduce_scatter(x)

    # small latency-bound reductions (softmax stats etc.) stay on the
    # primary path — the tuner would deactivate secondaries anyway.
    def tp_psum_small(self, x: jax.Array) -> jax.Array:
        if self.tp_axis is None or self.tp_size <= 1:
            return x
        return lax.psum(x, self.tp_axis)

    def tp_pmax_small(self, x: jax.Array) -> jax.Array:
        if self.tp_axis is None or self.tp_size <= 1:
            return x
        return lax.pmax(x, self.tp_axis)

    def tp_index(self) -> jax.Array:
        if self.tp_axis is None or self.tp_size <= 1:
            return jnp.zeros((), jnp.int32)
        return lax.axis_index(self.tp_axis)

    # -- data-parallel collectives -------------------------------------------

    def dp_all_to_all(self, x: jax.Array, split_axis: int,
                      concat_axis: int) -> jax.Array:
        if self._dp_comm is None:
            return x
        return self._dp_comm.all_to_all(x, split_axis, concat_axis)

    # -- expert-parallel span (MoE ep_a2a dispatch, DESIGN.md §15) -------------

    @property
    def ep_axes(self) -> Tuple[str, ...]:
        """Mesh axes the expert dimension shards over, outermost first
        (pod, node, data) — exactly the tiers whose communicators the
        cluster composition spans, so ``ep_all_to_all`` and the expert
        PartitionSpec always agree on the combined rank order."""
        axes = []
        if self._pod_comm is not None:
            axes.append(self.pod_axis)
        if self._node_comm is not None:
            axes.append(self.node_axis)
        if self._dp_comm is not None:
            axes.append(self.dp_axis)
        elif self.dp_axis and self.dp_size > 1:
            axes.append(self.dp_axis)
        return tuple(axes)

    @property
    def ep_size(self) -> int:
        """Total expert-parallel ways: the product of the ep axes."""
        sizes = {self.pod_axis: self.pod_size, self.node_axis:
                 self.node_size, self.dp_axis: self.dp_size}
        s = 1
        for a in self.ep_axes:
            s *= sizes[a]
        return s

    def ep_spec_axis(self):
        """The expert-dim PartitionSpec entry: None / a bare axis name /
        the outermost-major axis tuple — what ``param_specs`` shards the
        expert dimension by."""
        axes = self.ep_axes
        if not axes:
            return None
        if len(axes) == 1:
            return axes[0]
        return axes

    def ep_all_to_all(self, x: jax.Array, split_axis: int,
                      concat_axis: int) -> jax.Array:
        """Expert-dispatch all_to_all over the full ep span.  On a
        cluster mesh this is the rail-local decomposition of
        ``ClusterCommunicator.ep_all_to_all`` (intra shuffle + rail-
        aligned NIC leg + spine leg); single-node meshes keep the flat
        FlexLink-backed data-axis all_to_all, byte-identically."""
        if self._cluster_comm is not None:
            return self._cluster_comm.ep_all_to_all(x, split_axis,
                                                    concat_axis)
        return self.dp_all_to_all(x, split_axis, concat_axis)

    def dp_psum(self, x: jax.Array) -> jax.Array:
        if self.dp_axis is None or self.dp_size <= 1:
            return x
        return lax.psum(x, self.dp_axis)

    def dp_index(self) -> jax.Array:
        if self.dp_axis is None or self.dp_size <= 1:
            return jnp.zeros((), jnp.int32)
        return lax.axis_index(self.dp_axis)

    def dp_psum_small(self, x: jax.Array) -> jax.Array:
        if self.dp_axis is None or self.dp_size <= 1:
            return x
        return lax.psum(x, self.dp_axis)

    def dp_pmax_small(self, x: jax.Array) -> jax.Array:
        if self.dp_axis is None or self.dp_size <= 1:
            return x
        return lax.pmax(x, self.dp_axis)

    def pod_psum(self, x: jax.Array) -> jax.Array:
        """Plain pod-axis (DCN) reduction — the legacy pod-only
        production mesh, where the pod tier has no modeled link pool.
        On a 3-tier cluster mesh the pod axis rides its own flex
        communicator instead (see grad_all_reduce / ep_all_to_all)."""
        if self.pod_axis is None or self.pod_size <= 1:
            return x
        return lax.psum(x, self.pod_axis)

    def metrics_reduce(self, sums: Dict[str, jax.Array],
                       means: Optional[Dict[str, jax.Array]] = None
                       ) -> Dict[str, jax.Array]:
        """ONE stacked small-payload reduction for all step metrics.

        Replaces the nested ``pod_psum(node_psum(dp_psum(...)))`` chain —
        three latency-bound collectives per metric per step — with a
        single ``lax.psum`` of one stacked fp32 vector over the tuple of
        present gradient axes (data, node, pod).  ``sums`` entries come
        back globally summed (the loss, pre-scaled per shard); ``means``
        entries come back divided by the participating rank count (for
        values replicated across those axes — grad_norm, lr — the mean IS
        the value).  Axes of size 1 drop out; with no live axis the
        inputs pass through unchanged."""
        means = means or {}
        present = [(a, s) for a, s in ((self.dp_axis, self.dp_size),
                                       (self.node_axis, self.node_size),
                                       (self.pod_axis, self.pod_size))
                   if a is not None and s > 1]
        if not present:
            return {**sums, **means}
        vals = [jnp.asarray(v, jnp.float32).reshape(())
                for v in list(sums.values()) + list(means.values())]
        red = lax.psum(jnp.stack(vals), tuple(a for a, _ in present))
        n_ranks = 1
        for _, s in present:
            n_ranks *= s
        out: Dict[str, jax.Array] = {}
        for i, k in enumerate(sums):
            out[k] = red[i]
        for j, k in enumerate(means):
            out[k] = red[len(sums) + j] / n_ranks
        return out

    # -- node-axis (NIC tier) collectives --------------------------------------

    def node_psum(self, x: jax.Array) -> jax.Array:
        """Plain node-axis reduction — small latency-bound payloads
        (metrics), where the NIC-tier tuner would deactivate secondaries
        anyway."""
        if self.node_axis is None or self.node_size <= 1:
            return x
        return lax.psum(x, self.node_axis)

    def node_all_reduce(self, x: jax.Array) -> jax.Array:
        """Bandwidth-bound node-axis reduction through the NIC tier's
        flex communicator (rail/xrail/host_tcp pool) when one is live."""
        if self._node_comm is None:
            return self.node_psum(x)
        return self._node_comm.all_reduce(x)

    def grad_all_reduce(self, grads):
        """Gradient reduction over data, node and pod axes.

        With a node axis this is the hierarchical AllReduce of
        ``repro.cluster`` (DESIGN.md §9, §15): per-tier flex
        reduce-scatter down the chain, top-tier flex all-reduce on the
        smallest shard, per-tier flex all-gather back — each leg its own
        RoutePlan.  When the pod tier has its own communicator the pod
        axis is part of that composition; otherwise (legacy pod-only
        mesh, or no pod axis) any pod reduction stays a plain psum.
        Single-node meshes keep the flat FlexLink-backed data-axis
        reduce."""
        def red(g):
            if self._cluster_comm is not None:
                g = self._cluster_comm.all_reduce(g)
                if self._pod_comm is None:
                    g = self.pod_psum(g)
                return g
            if self._dp_comm is not None:
                g = self._dp_comm.all_reduce(g)
            elif self.dp_axis and self.dp_size > 1:
                g = lax.psum(g, self.dp_axis)
            return self.pod_psum(g)
        return jax.tree.map(red, grads)

    def expert_grad_reduce(self, g: jax.Array) -> jax.Array:
        """Reduce one ep_a2a expert grad over the gradient axes OUTSIDE
        the expert-parallel span.  The backward all_to_all already
        accumulated expert grads across every ep tier (data, plus node
        and pod when their communicators are live), so only the
        remaining replicated axes need a reduce — and each is a plain
        psum (there is no modeled link pool behind them by
        construction).  Single-node ep keeps the legacy behavior: no
        node axis, pod stays a psum."""
        if self._node_comm is None:
            # ep spans the data axis only — node (absent) and pod
            # (legacy production mesh) are replicated axes
            return self.pod_psum(g)
        if self._pod_comm is None:
            return self.pod_psum(g)
        return g

    # -- sizing helpers --------------------------------------------------------

    def shard(self, n: int, what: str = "dim") -> int:
        assert n % max(self.tp_size, 1) == 0, \
            f"{what}={n} not divisible by tp={self.tp_size}"
        return n // max(self.tp_size, 1)


def single_device_ctx() -> ParallelCtx:
    return ParallelCtx()
