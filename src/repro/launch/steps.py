"""Sharded step builders: wrap the model engine's step functions in
shard_map over a mesh, wiring the ParallelCtx (and therefore the FlexLink
RoutePlan engine) to the mesh axes.

Every launcher (train.py, serve.py, dryrun.py) builds its steps here so the
dry-run lowers EXACTLY what training/serving would run.  Communicators are
memoized per (axis, config) by ``comm_init_rank``, so rebuilding a step
after a Stage-2 share move re-traces against the SAME balancer state — only
the RoutePlans change (a plan-cache re-trace, visible in
``ctx.comm_report()``).

Two tiers per step kind:

* ``build_*_step``    — one jitted callable + ctx (tests, single traces);
* ``build_*_program`` — a :class:`~repro.runtime.program.StepProgram`
  wrapping the SAME builder: the plan-keyed executable cache plus a
  per-program Stage-2 replay recorder (DESIGN.md §7).  The launchers and
  the dry-run all go through programs, so what the dry-run lowers is
  byte-for-byte what the live loops execute.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.communicator import CommConfig
from repro.launch.mesh import mesh_dims, mesh_nodes
from repro.launch import shapes as SH
from repro.models.config import ArchConfig
from repro.models.tp import ParallelCtx
from repro.models.transformer import (decode_step, forward, init_params,
                                      lm_logits_local, lm_loss, param_specs)
from repro.optim.adamw import AdamWConfig, AdamWState, init_state
from repro.runtime.program import StepProgram
from repro.train.train_step import ef_init_residuals, make_train_step


def make_ctx(mesh: Mesh, comm: Optional[CommConfig] = None,
             cluster=None) -> ParallelCtx:
    """A mesh with a "node" axis gets the cluster wiring (DESIGN.md §9):
    the NIC-tier communicator on that axis and hierarchical gradient
    reduction.  ``cluster`` names the ClusterTopology; the default is
    synthesized from the comm profile (cluster_for)."""
    pods, dp, tp = mesh_dims(mesh)
    nodes = mesh_nodes(mesh)
    return ParallelCtx(
        tp_axis="model" if tp > 1 else None,
        dp_axis="data" if dp > 1 else None,
        node_axis="node" if nodes > 1 else None,
        pod_axis="pod" if pods > 1 else None,
        tp_size=tp, dp_size=dp, node_size=nodes, pod_size=pods,
        comm_config=comm or CommConfig(), cluster=cluster)


def opt_state_specs(psp) -> AdamWState:
    return AdamWState(step=P(), mu=psp, nu=psp)


def _batch_specs(cfg: ArchConfig, shape: SH.InputShape, mesh) -> Dict:
    pods, dp, tp = mesh_dims(mesh)
    return SH.input_partition_specs(cfg, shape, tp=tp, dp=dp, pods=pods,
                                    nodes=mesh_nodes(mesh))


def _named(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _train_state_specs(cfg: ArchConfig, ctx: ParallelCtx,
                       bucket_mb: float) -> Tuple[Any, Any]:
    # the expert dim shards over the ctx's ep span (data, plus node/pod
    # on a cluster mesh — DESIGN.md §15); ctx and specs must agree on
    # the combined rank order, so the ctx is the single authority
    psp = param_specs(cfg, data_axis=ctx.ep_spec_axis() or "data")
    osp = opt_state_specs(psp)
    if bucket_mb > 0 and ctx.ef_codec_name():
        # lossy wire codec + bucketed sync: the opt state is
        # (AdamWState, residuals) — the error-feedback residual tree is
        # param-shaped, so it shards exactly like the params
        osp = (osp, psp)
    return psp, osp


def init_train_state(cfg: ArchConfig, mesh: Mesh, ctx: ParallelCtx, key, *,
                     bucket_mb: float = 0.0):
    """(params, opt_state) built directly in the train step's layout.
    Each device materializes only its own shards, so a model whose
    unsharded params + AdamW moments exceed one device still starts."""
    psp, osp = _train_state_specs(cfg, ctx, bucket_mb)

    def init(key):
        params = init_params(key, cfg)
        opt_state = init_state(params)
        if not isinstance(osp, AdamWState):     # (AdamWState, residuals)
            opt_state = (opt_state, ef_init_residuals(params))
        return params, opt_state

    return jax.jit(init, out_shardings=(_named(mesh, psp),
                                        _named(mesh, osp)))(key)


def _train_builder(cfg: ArchConfig, mesh: Mesh, *,
                   comm: Optional[CommConfig],
                   opt: Optional[AdamWConfig],
                   shape: Optional[SH.InputShape],
                   remat: bool, cluster=None, bucket_mb: float = 0.0):
    ctx = make_ctx(mesh, comm, cluster=cluster)
    opt = opt or AdamWConfig()
    shape = shape or SH.SHAPES["train_4k"]
    psp, osp = _train_state_specs(cfg, ctx, bucket_mb)
    bsp = _batch_specs(cfg, shape, mesh)
    state_sh = (_named(mesh, psp), _named(mesh, osp))

    def builder():
        # a FRESH closure + jit per build: jax.jit memoizes per function
        # identity, so re-jitting a stale function object would silently
        # reuse the pre-share-move trace.
        step = make_train_step(cfg, ctx, opt, remat=remat,
                               bucket_mb=bucket_mb)
        sharded = shard_map(step, mesh=mesh,
                            in_specs=(psp, osp, bsp),
                            out_specs=(psp, osp, P()),
                            check_vma=False)
        # donate params + optimizer state: they are consumed and re-emitted
        # every step — aliasing halves the peak parameter memory.  The
        # explicit shardings let jit pair each donated buffer with the
        # output of the same layout: left to XLA, a buffer could alias an
        # output of equal global shape but another per-device shape.
        return jax.jit(sharded, donate_argnums=(0, 1),
                       in_shardings=(*state_sh, _named(mesh, bsp)),
                       out_shardings=(*state_sh,
                                      NamedSharding(mesh, P())))

    return builder, ctx


def build_train_step(cfg: ArchConfig, mesh: Mesh, *,
                     comm: Optional[CommConfig] = None,
                     opt: Optional[AdamWConfig] = None,
                     shape: Optional[SH.InputShape] = None,
                     remat: bool = True, cluster=None,
                     bucket_mb: float = 0.0):
    """jit(shard_map(train_step)) with full param/opt/batch shardings."""
    builder, ctx = _train_builder(cfg, mesh, comm=comm, opt=opt,
                                  shape=shape, remat=remat, cluster=cluster,
                                  bucket_mb=bucket_mb)
    return builder(), ctx


def build_train_program(cfg: ArchConfig, mesh: Mesh, *,
                        comm: Optional[CommConfig] = None,
                        opt: Optional[AdamWConfig] = None,
                        shape: Optional[SH.InputShape] = None,
                        remat: bool = True,
                        name: str = "", cluster=None,
                        bucket_mb: float = 0.0):
    """The train step as a StepProgram: plan-keyed executable cache +
    isolated Stage-2 replay recorder.  ``bucket_mb > 0`` turns on the
    bucketed overlapped gradient sync (DESIGN.md §11)."""
    builder, ctx = _train_builder(cfg, mesh, comm=comm, opt=opt,
                                  shape=shape, remat=remat, cluster=cluster,
                                  bucket_mb=bucket_mb)
    return StepProgram(builder, ctx, name=name), ctx


def _prefill_builder(cfg: ArchConfig, mesh: Mesh, *,
                     comm: Optional[CommConfig],
                     shape: Optional[SH.InputShape],
                     remat: bool, cluster=None):
    ctx = make_ctx(mesh, comm, cluster=cluster)
    shape = shape or SH.SHAPES["prefill_32k"]
    psp = param_specs(cfg, data_axis=ctx.ep_spec_axis() or "data")
    bsp = _batch_specs(cfg, shape, mesh)
    pods, dp, tp = mesh_dims(mesh)
    ba = SH.batch_axes(pods, mesh_nodes(mesh))

    def builder():
        def prefill(params, batch):
            x, _ = forward(params, batch["tokens"], cfg, ctx,
                           vis_embed=batch.get("vis_embed"),
                           enc_embed=batch.get("enc_embed"), remat=remat)
            return lm_logits_local(params, x[:, -1:], cfg, ctx)[:, 0]

        sharded = shard_map(prefill, mesh=mesh, in_specs=(psp, bsp),
                            out_specs=P(ba, "model"), check_vma=False)
        return jax.jit(sharded)

    return builder, ctx


def build_prefill_step(cfg: ArchConfig, mesh: Mesh, *,
                       comm: Optional[CommConfig] = None,
                       shape: Optional[SH.InputShape] = None,
                       remat: bool = True, cluster=None):
    """Forward-only prefill: returns last-position local-vocab logits."""
    builder, ctx = _prefill_builder(cfg, mesh, comm=comm, shape=shape,
                                    remat=remat, cluster=cluster)
    return builder(), ctx


def build_prefill_program(cfg: ArchConfig, mesh: Mesh, *,
                          comm: Optional[CommConfig] = None,
                          shape: Optional[SH.InputShape] = None,
                          remat: bool = True,
                          name: str = "", cluster=None):
    builder, ctx = _prefill_builder(cfg, mesh, comm=comm, shape=shape,
                                    remat=remat, cluster=cluster)
    return StepProgram(builder, ctx, name=name), ctx


def _serve_builder(cfg: ArchConfig, mesh: Mesh, shape: SH.InputShape, *,
                   comm: Optional[CommConfig], cluster=None):
    ctx = make_ctx(mesh, comm, cluster=cluster)
    pods, dp, tp = mesh_dims(mesh)
    dcfg = SH.decode_config(cfg, shape, tp=tp, dp=dp)
    psp = param_specs(cfg, data_axis=ctx.ep_spec_axis() or "data")
    isp = SH.input_partition_specs(cfg, shape, tp=tp, dp=dp, pods=pods)
    tok_b = isp["token"][0]
    out_logits = P(tok_b, "model")      # [B, V_local] — vocab stays sharded

    def builder():
        def serve(params, cache, token, pos):
            logits_l, cache = decode_step(params, cache, token, pos, cfg,
                                          ctx, dcfg)
            return logits_l, cache

        sharded = shard_map(serve, mesh=mesh,
                            in_specs=(psp, isp["cache"], isp["token"],
                                      isp["pos"]),
                            out_specs=(out_logits, isp["cache"]),
                            check_vma=False)
        # donate the KV cache: it is updated in place every decode step.
        return jax.jit(sharded, donate_argnums=(1,))

    return builder, ctx, dcfg


def build_serve_step(cfg: ArchConfig, mesh: Mesh, shape: SH.InputShape, *,
                     comm: Optional[CommConfig] = None, cluster=None):
    """One-token decode with a seq_len KV cache (decode_32k / long_500k)."""
    builder, ctx, dcfg = _serve_builder(cfg, mesh, shape, comm=comm,
                                        cluster=cluster)
    return builder(), ctx, dcfg


def build_serve_program(cfg: ArchConfig, mesh: Mesh, shape: SH.InputShape, *,
                        comm: Optional[CommConfig] = None,
                        name: str = "", cluster=None):
    builder, ctx, dcfg = _serve_builder(cfg, mesh, shape, comm=comm,
                                        cluster=cluster)
    return StepProgram(builder, ctx, name=name), ctx, dcfg


def eval_shape_params(cfg: ArchConfig):
    """ShapeDtypeStruct param tree — NO allocation (dry-run pattern)."""
    return jax.eval_shape(
        lambda key: init_params(key, cfg), jax.random.PRNGKey(0))


def eval_shape_opt_state(params_sds) -> AdamWState:
    mu = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params_sds)
    return AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32), mu=mu,
                      nu=jax.tree.map(lambda x: x, mu))
