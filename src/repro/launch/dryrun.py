import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  512 placeholder CPU devices back the production
# meshes: (16,16) single-pod and (2,16,16) multi-pod.

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro.configs import ALIASES, ARCH_IDS, get_config       # noqa: E402
from repro.core.communicator import CommConfig                # noqa: E402
from repro.launch import shapes as SH                         # noqa: E402
from repro.launch.mesh import (make_production_mesh, mesh_dims,
                               mesh_nodes)                     # noqa: E402
from repro.launch.steps import (build_prefill_program, build_serve_program,
                                build_train_program, eval_shape_opt_state,
                                eval_shape_params)             # noqa: E402

"""Multi-pod dry-run driver.

For every (architecture x input shape x mesh) this lowers + compiles the
EXACT step the launchers run — ShapeDtypeStruct inputs, no allocation —
then records memory_analysis(), cost_analysis() and the HLO collective
bytes for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b \
      --shape train_4k --mesh single --out results/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""


def _sds_batch(cfg, shape, mesh):
    pods, dp, tp = mesh_dims(mesh)
    return SH.input_specs(cfg, shape, tp=tp, dp=dp, pods=pods)


def default_node_split(nodes: int, pods: int = 1):
    """(data, model) split for an N-node mesh with no --mesh-split: the
    largest power-of-two pod slice that fits the 512 forced CPU devices
    (pods * nodes * d * m <= 512), model axis first up to the production
    16."""
    budget = max(512 // max(nodes * max(pods, 1), 1), 1)
    m = min(budget, 16)
    return (max(budget // m, 1), m)


def node_layout(nodes: int, mesh_split, pods: int = 1):
    """The (data, model) split an N-node run uses — ONE derivation shared
    by run_one (which builds the mesh from it) and main (which names the
    result-cache file from it), so the cache tag can never describe a
    different layout than the one that actually ran."""
    return (tuple(mesh_split) if mesh_split is not None
            else default_node_split(nodes, pods))


def run_one(arch: str, shape_name: str, multi_pod: bool,
            backend: str = "flexlink", mesh_split=None,
            remat=True, variant: str = "",
            tuning_cache: str = "", secondary_algo: str = "ring",
            nodes: int = 1, cluster_name: str = "",
            degrade: str = "", bucket_mb: float = 0.0,
            compress: str = "", fault: str = "",
            cluster_pods: int = 0) -> dict:
    """mesh_split: optional (data, model) reshape of the 256-chip pod —
    the TP-degree tuning lever of EXPERIMENTS §Perf.  remat: True | False |
    "dots" (selective checkpointing).  tuning_cache: TuningProfile JSON —
    Stage-1 shares warm-start from it and are saved back after lowering,
    so a later dry-run (or live launch) skips the profiling phase.
    nodes > 1 prepends a simulated "node" axis (repro.cluster): the step
    lowers the two-tier hierarchical gradient sync and the NIC tier's
    slots tune (and warm-start) like any other.
    cluster_pods > 1 prepends a "pod" axis above the node axis: the step
    lowers the THREE-level hierarchical sync over the pod/DCN tier and
    MoE dispatch becomes the rail-local ep all_to_all (DESIGN.md §15).
    degrade: a ``name[:member]=factor`` fault spec (DESIGN.md §10):
    scales one link member's effective bandwidth — the degraded tier
    profile gets a distinct name, so its tuning (which drains exactly the
    sick member) keys separate TuningProfile entries from the healthy
    fabric's.
    compress: secondary-path wire-codec spec (DESIGN.md §12, e.g.
    ``secondary=fp8``): the tuner prices wire bytes per codec and the
    per-slot wire table below shows what each path actually ships."""
    cfg = get_config(arch)
    shape = SH.SHAPES[shape_name]
    if cfg.mla is not None and shape.kind == "decode":
        raise ValueError(
            f"{arch}: latent attention (MLA) has no dense K/V cache for the "
            f"{shape_name} decode step; it decodes through PagedServeEngine "
            f"and its latent paged pool (repro.launch.serve --paged on)")
    from repro.configs.clusters import resolve_cluster, resolve_faults
    cluster, nodes, cluster_pods = resolve_cluster(cluster_name, nodes,
                                                   cluster_pods)
    cluster, intra_profile, timeline = resolve_faults(
        cluster, nodes, cluster.node.name if cluster else "tpu_v5e",
        degrade=degrade, fault=fault, pods=cluster_pods)
    if cluster_pods > 1 and nodes <= 1:
        raise ValueError("--pods > 1 needs a multi-node run (--nodes or a "
                         "3-tier --cluster): the pod tier composes above "
                         "the NIC tier")
    if nodes > 1:
        if multi_pod:
            raise ValueError("--nodes does not combine with the multi-pod "
                             "mesh (pick one outer axis)")
        from repro.launch.mesh import make_cluster_mesh
        split = node_layout(nodes, mesh_split, cluster_pods)
        mesh = make_cluster_mesh(nodes, *split, pods=cluster_pods)
        mesh_name = f"nodes{nodes}x{split[0]}x{split[1]}"
        if cluster_pods > 1:
            mesh_name = f"pods{cluster_pods}-" + mesh_name
    elif mesh_split is not None and not multi_pod:
        import jax as _jax
        mesh = _jax.make_mesh(tuple(mesh_split), ("data", "model"))
        mesh_name = f"single{mesh_split[0]}x{mesh_split[1]}"
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "multi" if multi_pod else "single"
    chips = int(np.prod(mesh.devices.shape))
    # runtime_balancing=False already keeps these trace-only steps out of
    # any Stage-2 replay log (plan_for skips the append), and the differing
    # config fields give the dry-run its own memoized communicator; the tag
    # just makes the isolation intent explicit in the registry key.
    # A named cluster sets the intra profile: its node type IS the machine
    # the run models (the ParallelCtx cross-check would reject a mismatch).
    comm = CommConfig(backend=backend,
                      profile=intra_profile,
                      runtime_balancing=False, tag="dryrun",
                      tuning_cache=tuning_cache,
                      secondary_algo=secondary_algo,
                      compress=compress,
                      fault=timeline.spec() if timeline else "")
    pods, dp, tp = mesh_dims(mesh)
    t0 = time.time()

    params_sds = eval_shape_params(cfg)
    batch_sds = _sds_batch(cfg, shape, mesh)

    prog = None
    try:
        with mesh:
            # StepPrograms here too: the dry-run lowers through the exact
            # same builder (and replay-recorder scope) the live loops
            # execute, so the lowered HLO is byte-for-byte what
            # training/serving runs.
            if shape.kind == "train":
                prog, ctx = build_train_program(cfg, mesh, comm=comm,
                                                shape=shape, remat=remat,
                                                cluster=cluster,
                                                bucket_mb=bucket_mb)
                opt_sds = eval_shape_opt_state(params_sds)
                if bucket_mb > 0 and ctx.ef_codec_name():
                    # lossy wire codec: error-feedback residuals ride the
                    # opt state, param-shaped (train_step.py docstring)
                    opt_sds = (opt_sds, params_sds)
                lowered = prog.lower(params_sds, opt_sds, batch_sds)
            elif shape.kind == "prefill":
                prog, ctx = build_prefill_program(cfg, mesh, comm=comm,
                                                  shape=shape,
                                                  cluster=cluster)
                lowered = prog.lower(params_sds, batch_sds)
            else:
                prog, ctx, dcfg = build_serve_program(cfg, mesh, shape,
                                                      comm=comm,
                                                      cluster=cluster)
                lowered = prog.lower(params_sds, batch_sds["cache"],
                                     batch_sds["token"], batch_sds["pos"])
            t_lower = time.time() - t0
            hlo_text = lowered.as_text()
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            # warm/cold Stage-1 provenance per slot, before the program is
            # retired — and persist the shares for the next launch
            tuning_status = ctx.tuning_status()
            comm_rep = ctx.comm_report()
            if tuning_cache:
                ctx.save_tuning_profile(tuning_cache)
    finally:
        # retire the probe program even on failure: a --all sweep builds
        # one per (arch, shape, mesh) against memoized communicators and
        # main() catches per-pair exceptions
        if prog is not None:
            prog.close()

    # fault-transition table (repro.faults, DESIGN.md §14): a dry-run
    # never advances fabric time, so this is the STATIC projection —
    # when each scheduled event fires and when it would commit under the
    # FabricClock's hysteresis
    fault_proj = []
    if timeline is not None:
        from repro.faults import FabricClock
        fault_proj = FabricClock(timeline).projection()
        for row in fault_proj:
            print(f"  [fault] step {row['step']:>5d} {row['kind']:<7s} "
                  f"{row['event']} (commits at step {row['commit_step']})",
                  flush=True)

    # per-member share table (the observability satellite of DESIGN.md
    # §10): one row per multi-member link per tuned slot — on a degraded
    # run this is where a single drained rail is visible next to its
    # still-loaded siblings
    for axis, slots in sorted(tuning_status.items()):
        for slot_name, st in sorted(slots.items()):
            for link, weights in sorted((st.get("members") or {}).items()):
                total = sum(weights.values()) or 1
                cells = " ".join(f"{m}={w}({w / total:.0%})"
                                 for m, w in weights.items())
                print(f"  [members] {axis}/{slot_name} {link}: {cells}",
                      flush=True)

    # per-slot wire table (DESIGN.md §12): logical vs wire bytes + codec
    # id per path, and the aggregate wire scale the roofline below uses
    # to shrink the collective term
    wire_logical = wire_total = 0.0
    for axis, rep in sorted(comm_rep.items()):
        if not isinstance(rep, dict):
            continue
        for slot_name, desc in sorted(rep.items()):
            if not isinstance(desc, dict) or "wire" not in desc:
                continue
            w = desc["wire"]
            wire_logical += w["logical_bytes"]
            wire_total += w["wire_bytes"]
            if desc.get("codecs"):
                cells = " ".join(
                    f"{p}={row['codec']}"
                    f"({row['logical_bytes']}->{row['wire_bytes']}B)"
                    for p, row in sorted(w["paths"].items()))
                print(f"  [wire] {axis}/{slot_name}: {cells} "
                      f"saved={w['bytes_saved']}B", flush=True)
    wire_scale = (wire_total / wire_logical
                  if compress and wire_logical else 1.0)

    # cluster rollup + MoE-dispatch split (DESIGN.md §15): the composed
    # tiers' slot rollups ride the record, and the a2a block shows how
    # dispatch bytes divided between rail-local NIC legs and the spine
    cluster_rep = (comm_rep.get("cluster")
                   if isinstance(comm_rep, dict) else None)
    if isinstance(cluster_rep, dict) and "a2a" in cluster_rep:
        a2a = cluster_rep["a2a"]
        print(f"  [a2a] rail_local={a2a['rail_local_bytes']}B "
              f"spine={a2a['spine_bytes']}B intra={a2a['intra_bytes']}B "
              f"rail_balance={a2a['rail_balance']:.2f} ({a2a['source']})",
              flush=True)

    cost = compiled.cost_analysis() or {}
    mem = None
    mem_report = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem_report = {
                k: int(getattr(ma, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, k)}
            mem = sum(v for k, v in mem_report.items()
                      if k != "generated_code_size_in_bytes")
    except Exception as e:  # CPU backend may not implement it
        mem_report = {"error": str(e)}

    # --- roofline ---------------------------------------------------------
    # PRIMARY: analytic op inventory (exact — see roofline/analytic.py for
    # why raw cost_analysis cannot be used: XLA CPU counts scan bodies once).
    # The HLO text still validates the collective STRUCTURE (kinds + axes).
    from repro.roofline.analysis import (parse_collectives, PEAK_FLOPS,
                                         HBM_BW, ICI_BW)
    from repro.roofline.analytic import cost_model
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    # the node axis is an outer data-parallel dimension for the analytic
    # cost model (its collective bytes ride the NIC tier, not ICI)
    cm = cost_model(cfg, shape, tp=tp, dp=dp * mesh_nodes(mesh), pods=pods,
                    backend=backend, remat=remat,
                    # 3-tier cluster mesh: experts shard over the full ep
                    # span, so the pod AR excludes expert params
                    ep_over_pods=cluster_pods > 1)
    t_compute = cm.flops_total / (chips * PEAK_FLOPS)
    t_memory = cm.hbm_bytes / (chips * HBM_BW)
    t_collective = cm.collective_bytes / (chips * ICI_BW)
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_collective}
    dominant = max(terms, key=terms.get)
    # serial + overlap-aware step-time bounds (DESIGN.md §11): n_buckets
    # from the per-rank grad payload vs the requested bucket size; 1
    # (monolithic) makes the two bounds coincide.
    from repro.roofline.analytic import step_time_bounds
    if bucket_mb > 0 and shape.kind == "train":
        grad_bytes = (cm.params / max(tp, 1)) * 4
        n_buckets = max(int(np.ceil(grad_bytes / (bucket_mb * 2 ** 20))), 1)
    else:
        n_buckets = 1
    bounds = step_time_bounds(t_compute, t_memory, t_collective,
                              n_buckets=n_buckets, wire_scale=wire_scale)
    model_flops = 6.0 * cm.active_params * (
        shape.global_batch * (shape.seq_len if shape.kind == "train" else 1))
    if shape.kind != "train":
        model_flops = 2.0 * cm.active_params * shape.global_batch * (
            shape.seq_len if shape.kind == "prefill" else 1)
    hlo_colls = parse_collectives(hlo_text, mesh_shape)
    hlo_coll_struct = {}
    for c in hlo_colls:
        k = f"{c.op}@{c.axis}"
        hlo_coll_struct[k] = hlo_coll_struct.get(k, 0) + 1
    roofline = {
        "chips": chips,
        "flops_fwd": cm.flops_fwd, "flops_total": cm.flops_total,
        "hbm_bytes": cm.hbm_bytes,
        "collective_bytes_total": cm.collective_bytes,
        "collective_by_axis": cm.coll_by_axis(),
        "collective_by_op": cm.coll_by_op(),
        "t_compute": t_compute, "t_memory": t_memory,
        "t_collective": t_collective, "dominant": dominant,
        **bounds,
        "model_flops": model_flops,
        "useful_flops_ratio": model_flops / cm.flops_total
        if cm.flops_total else 0.0,
        "params": cm.params, "active_params": cm.active_params,
        "memory_per_chip": mem,
    }
    if compress:
        # only on compressed runs: the default dry-run record stays
        # byte-identical to pre-codec outputs
        roofline["wire_scale"] = wire_scale
        roofline["wire_bytes_saved"] = int(wire_logical - wire_total)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "backend": backend, "chips": chips, "ok": True,
        "variant": variant, "remat": str(remat),
        "degrade": degrade,
        **({"fault": fault, "faults": fault_proj} if fault else {}),
        **({"compress": compress} if compress else {}),
        **({"cluster": cluster_rep} if isinstance(cluster_rep, dict)
           else {}),
        "tuning": tuning_status,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem_report,
        "hlo_cost_analysis_raw": {
            "flops_per_device_scanbody_once": float(cost.get("flops", 0.0)),
            "bytes_per_device_scanbody_once": float(
                cost.get("bytes accessed", 0.0)),
            "caveat": "XLA CPU cost_analysis counts lax.scan bodies once; "
                      "see roofline/analytic.py",
        },
        "hlo_collective_structure": hlo_coll_struct,
        "roofline": roofline,
    }
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALIASES) + ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SH.SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--backend", choices=["flexlink", "nccl"],
                    default="flexlink")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) pair")
    ap.add_argument("--out", default="results/dryrun",
                    help="output dir (one json per pair)")
    ap.add_argument("--mesh-split", default="",
                    help="d,m reshape of the single pod (e.g. 2,4) — "
                         "small splits make CI smoke runs cheap")
    ap.add_argument("--nodes", type=int, default=0,
                    help="simulated node count: prepends a 'node' axis "
                         "(repro.cluster) so the step lowers the two-tier "
                         "hierarchical gradient sync; combine with "
                         "--mesh-split to keep smoke runs cheap")
    ap.add_argument("--cluster", default="",
                    help="named cluster topology from configs/clusters.py "
                         "(default: synthesized from the tpu_v5e profile)")
    ap.add_argument("--pods", type=int, default=0,
                    help="simulated pod count: prepends a 'pod' axis above "
                         "the node axis so the step lowers the THREE-level "
                         "hierarchical sync over the pod/DCN tier and the "
                         "rail-local MoE all_to_all (DESIGN.md §15).  A "
                         "3-tier --cluster implies its pod count")
    ap.add_argument("--degrade", default="",
                    help="fault injection name[:member]=factor: scale one "
                         "link member's effective bandwidth (e.g. "
                         "rail3=0.25 drains one NIC rail to quarter "
                         "health; pcie=0.5 throttles the whole host "
                         "path).  The degraded fabric keys its own "
                         "TuningProfile entries")
    ap.add_argument("--fault", default="",
                    help="fault-timeline schedule (repro.faults, DESIGN.md "
                         "§14), e.g. 'rail3@step200=0.25,node1@step400="
                         "down'.  The dry-run validates the schedule "
                         "against the run's fabric and prints the static "
                         "fault-transition table (fire + hysteresis-"
                         "commit steps); it never advances fabric time")
    ap.add_argument("--tuning-cache", default="",
                    help="TuningProfile JSON: warm-start Stage-1 and save "
                         "the converged shares back after lowering")
    ap.add_argument("--secondary-algo", choices=["ring", "tree"],
                    default="ring")
    ap.add_argument("--bucket-mb", type=float, default=0.0,
                    help="bucketed overlapped gradient sync: target bucket "
                         "size in MiB (train shapes; DESIGN.md §11).  "
                         "0 = monolithic sync, byte-identical plans to "
                         "pre-bucketing dry-runs")
    ap.add_argument("--compress", default="",
                    help="secondary-path wire codecs, e.g. 'secondary=fp8' "
                         "or 'staged=bf16,ortho=fp8' (DESIGN.md §12): the "
                         "tuner prices wire bytes per codec and the "
                         "per-slot wire table shows what each path ships")
    ap.add_argument("--assert-warm", action="store_true",
                    help="exit nonzero unless EVERY tuned slot was "
                         "warm-started with zero Stage-1 iterations")
    args = ap.parse_args(argv)
    mesh_split = (tuple(int(x) for x in args.mesh_split.split(","))
                  if args.mesh_split else None)
    from repro.configs.clusters import resolve_cluster
    _, nodes, pods = resolve_cluster(args.cluster, args.nodes, args.pods)

    pairs = []
    archs = sorted(ALIASES) if args.all else [args.arch]
    shapes_ = sorted(SH.SHAPES) if args.all else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes_:
            for m in meshes:
                pairs.append((a, s, m))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    cold_slots = 0
    checked_slots = 0
    for arch, shape_name, mesh_name in pairs:
        tag = f"{arch}__{shape_name}__{mesh_name}__{args.backend}"
        if nodes > 1:
            # encode the full layout (base mesh, pod/node counts, split,
            # named cluster) so runs differing in ANY of them never share
            # a cache file
            split = node_layout(nodes, mesh_split, pods)
            extra = f"nodes{nodes}x{split[0]}x{split[1]}"
            if pods > 1:
                extra = f"pods{pods}-" + extra
            if args.cluster:
                extra += f"-{args.cluster}"
            tag = (f"{arch}__{shape_name}__{mesh_name}-{extra}__"
                   f"{args.backend}")
        if args.degrade:
            # a degraded run prices a different fabric: never share a
            # result-cache file with the healthy run of the same layout
            safe = args.degrade.replace(":", "_").replace("=", "-")
            tag += f"__degrade-{safe}"
        if args.fault:
            # a fault schedule changes the record (transition table) and
            # the comm memo key — its own result-cache file
            safe = (args.fault.replace(":", "_").replace("=", "-")
                    .replace("@", "~").replace(",", "+"))
            tag += f"__fault-{safe}"
        if args.bucket_mb > 0:
            # a bucketed run lowers a different sync structure — its own
            # result-cache file
            tag += f"__bmb{args.bucket_mb:g}"
        if args.compress:
            # a compressed run prices (and may lower) different plans:
            # never share a result-cache file with the uncompressed run
            safe = (args.compress.replace(":", "_").replace("=", "-")
                    .replace(",", "+"))
            tag += f"__compress-{safe}"
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[skip] {tag} (cached)")
            continue
        print(f"[run ] {tag}", flush=True)
        try:
            rec = run_one(arch, shape_name, mesh_name == "multi",
                          args.backend, mesh_split=mesh_split,
                          tuning_cache=args.tuning_cache,
                          secondary_algo=args.secondary_algo,
                          nodes=nodes, cluster_name=args.cluster,
                          degrade=args.degrade, bucket_mb=args.bucket_mb,
                          compress=args.compress, fault=args.fault,
                          cluster_pods=pods)
        except Exception as e:
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "backend": args.backend, "ok": False, "error": repr(e)}
            failures += 1
        with open(path, "w") as f:
            json.dump(rec, f, indent=2, default=str)
        status = "OK" if rec.get("ok") else "FAIL"
        extra = ""
        if rec.get("ok"):
            r = rec["roofline"]
            slots = [s for ax in rec.get("tuning", {}).values()
                     for s in ax.values()]
            warm = sum(s["warm"] for s in slots)
            cold_slots += len(slots) - warm
            checked_slots += len(slots)
            extra = (f" dominant={r['dominant']}"
                     f" tc={r['t_compute']:.2e} tm={r['t_memory']:.2e}"
                     f" tl={r['t_collective']:.2e}"
                     f" compile={rec['compile_s']}s"
                     f" slots={warm}/{len(slots)} warm")
        print(f"[{status:4s}] {tag}{extra}", flush=True)
    if args.assert_warm and (cold_slots or not checked_slots):
        # zero checked slots (every pair skipped as cached, or nothing
        # tuned) must fail too: a vacuous pass verifies nothing
        what = (f"{cold_slots} slot(s) ran Stage-1 cold" if cold_slots
                else "no tuned slots were checked (cached/skipped runs?)")
        print(f"[FAIL] --assert-warm: {what} (expected a full warm-start "
              f"from {args.tuning_cache or '<no --tuning-cache>'})",
              flush=True)
        return 2
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
