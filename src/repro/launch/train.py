"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \
      --steps 50 --mesh-shape 2,4

``--smoke`` swaps in the reduced config (2 layers, d_model<=512) so the
launcher runs on CPU; ``--layers N`` keeps the published widths and cuts
only the depth, the size a chip run uses.  The mesh shape is
(data, model) — on real hardware use (16,16) per pod.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALIASES, get_config
from repro.core.communicator import CommConfig
from repro.data.pipeline import make_batches
from repro.launch import shapes as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (make_cluster_mesh, make_mesh,
                               make_production_mesh, mesh_dims, mesh_nodes)
from repro.launch.steps import build_train_program, init_train_state
from repro.optim.adamw import AdamWConfig
from repro.train.loop import LoopConfig, run_loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b", choices=sorted(ALIASES))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers at the config's own "
                         "widths (0 = the published depth)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh-shape", default="",
                    help="e.g. 2,4 = (data=2, model=4); empty = single dev")
    ap.add_argument("--nodes", type=int, default=0,
                    help="simulated node count: prepends a 'node' axis to "
                         "the mesh; gradient sync becomes the two-tier "
                         "hierarchical AllReduce over the cluster's NIC "
                         "tier (repro.cluster, DESIGN.md §9)")
    ap.add_argument("--cluster", default="",
                    help="named cluster topology from configs/clusters.py "
                         "(default: synthesized from the comm profile)")
    ap.add_argument("--pods", type=int, default=0,
                    help="simulated pod count: prepends a 'pod' axis to "
                         "the cluster mesh; gradient sync becomes the "
                         "three-level hierarchical AllReduce over the "
                         "pod/DCN tier (DESIGN.md §15).  A 3-tier "
                         "--cluster implies its pod count")
    ap.add_argument("--degrade", default="",
                    help="launch-time fault injection name[:member]=factor "
                         "(e.g. rail3=0.25): scale one link member's "
                         "effective bandwidth; Stage 2 drains exactly that "
                         "member (DESIGN.md §10).  Sugar for a step-0 "
                         "--fault event — both run through one parser")
    ap.add_argument("--fault", default="",
                    help="fault-timeline schedule (repro.faults, DESIGN.md "
                         "§14), e.g. 'rail3@step200=0.25,rail3@step600=1.0,"
                         "node1@step400=down': per-member degradation, "
                         "full-link loss (=down) and elastic whole-node "
                         "loss at step boundaries.  Transitions commit "
                         "through the FabricClock's hysteresis and warm-"
                         "start Stage 2 from the nearest TuningProfile "
                         "entry; node loss resumes from the latest "
                         "checkpoint at the surviving topology")
    ap.add_argument("--backend", choices=["flexlink", "nccl"],
                    default="flexlink")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint period in steps (0 = final only); an "
                         "elastic node-loss schedule needs one below the "
                         "fault horizon")
    ap.add_argument("--out", default="",
                    help="write a JSON run report (loss, program stats, "
                         "tuning provenance, fault transitions) — what the "
                         "fault-smoke CI asserts on")
    ap.add_argument("--tuning-cache", default="",
                    help="TuningProfile JSON: warm-start Stage-1 shares "
                         "from it and persist them back at the end")
    ap.add_argument("--timing", choices=["sim", "measured"], default="sim",
                    help="Stage-2 TimingSource: analytic simulator or "
                         "wall-clock step durations (control/timing.py)")
    ap.add_argument("--secondary-algo", choices=["ring", "tree"],
                    default="ring",
                    help="secondary-path collective algorithm (paper §6)")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="bucketed overlapped gradient sync: target bucket "
                         "size in MiB (DESIGN.md §11).  0 = monolithic "
                         "per-leaf sync (byte-identical plans to pre-"
                         "bucketing behavior)")
    ap.add_argument("--compress", default="",
                    help="secondary-path wire codecs (DESIGN.md §12), e.g. "
                         "'secondary=fp8' or 'staged=bf16,ortho=fp8'.  The "
                         "tuner still chooses per slot whether each codec "
                         "pays; lossy codecs add error-feedback residuals "
                         "to bucketed gradient sync.  Default: off — "
                         "byte-identical plans and tuning")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    shape = SH.InputShape("cli", "train", args.seq_len, args.batch)

    from repro.configs.clusters import resolve_cluster, resolve_faults
    cluster, n_nodes, n_pods = resolve_cluster(args.cluster, args.nodes,
                                               args.pods)
    cluster, intra_profile, timeline = resolve_faults(
        cluster, n_nodes, cluster.node.name if cluster else "tpu_v5e",
        degrade=args.degrade, fault=args.fault, pods=n_pods)

    if args.mesh_shape:
        dims = tuple(int(x) for x in args.mesh_shape.split(","))
    else:
        dims = (1, 1)
    if n_pods > 1 and n_nodes <= 1:
        raise SystemExit("--pods > 1 needs a multi-node cluster run "
                         "(--nodes/--cluster): the pod tier composes "
                         "above the NIC tier")
    if n_nodes > 1:
        if len(dims) != 2:
            raise SystemExit("--nodes combines with a 2-dim (data, model) "
                             "--mesh-shape only")
        mesh = make_cluster_mesh(n_nodes, *dims, pods=n_pods)
    else:
        mesh = make_mesh(dims, ("data", "model")[-len(dims):]
                         if len(dims) == 2 else ("pod", "data", "model"))
    pods, dp, tp = mesh_dims(mesh)
    nodes = mesh_nodes(mesh)
    assert args.batch % (dp * pods * nodes) == 0

    # a named cluster sets the intra profile: its node type IS the machine
    # being modelled (ParallelCtx cross-checks cluster vs profile)
    comm = CommConfig(backend=args.backend,
                      profile=intra_profile,
                      timing=args.timing,
                      secondary_algo=args.secondary_algo,
                      tuning_cache=args.tuning_cache,
                      compress=args.compress,
                      # canonical schedule spec: a faulted run must never
                      # share a memoized communicator with a fault-free one
                      fault=timeline.spec() if timeline else "")
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps)

    with mesh:
        # StepProgram: plan-keyed executable cache + per-program Stage-2
        # replay recorder — the loop never re-jits a plan it already
        # compiled (DESIGN.md §7).
        program, ctx = build_train_program(cfg, mesh, comm=comm, opt=opt,
                                           shape=shape, cluster=cluster,
                                           bucket_mb=args.bucket_mb)
        # a lossy wire codec adds the error-feedback residuals to the
        # optimizer state (train_step.py docstring)
        params, opt_state = init_train_state(
            cfg, mesh, ctx, jax.random.PRNGKey(0), bucket_mb=args.bucket_mb)
        batches_fn = lambda: make_batches(  # noqa: E731
            cfg, seq_len=args.seq_len, batch_per_shard=args.batch)
        clock = handler = None
        if timeline is not None:
            from repro.faults import FabricClock, make_train_resume
            clock = FabricClock(timeline).attach(ctx)
            if any(e.kind == "node" for e in timeline.events):
                handler = make_train_resume(
                    cfg, opt=opt, shape=shape, comm_config=comm,
                    cluster=cluster, dp=dp, tp=tp,
                    ckpt_dir=args.ckpt_dir, batches_fn=batches_fn,
                    bucket_mb=args.bucket_mb)
        loop = LoopConfig(total_steps=args.steps, log_every=5,
                          ckpt_every=args.ckpt_every,
                          ckpt_dir=args.ckpt_dir or None,
                          tuning_cache=args.tuning_cache or None,
                          faults=clock, on_node_loss=handler)
        try:
            params, opt_state, hist = run_loop(program, params, opt_state,
                                               batches_fn(), ctx, loop)
        finally:
            program.close()     # retire the recorder on the memoized comms
    print(f"final loss: {hist[-1]:.4f} (from {hist[0]:.4f})")
    if args.out:
        import json
        import os
        rep = {"final_loss": hist[-1], "losses": hist, "steps": args.steps,
               **(loop.report or {})}
        if clock is not None:
            rep["faults"] = clock.report()
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rep, f, indent=2, default=str)
        print(f"run report -> {args.out}")
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
