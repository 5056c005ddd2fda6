"""Serving launcher: batched request serving — wave engine or the
continuous-batching paged engine (DESIGN.md §13).

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
      --requests 8 --max-new 12 --paged on --kv-block 16 \
      --max-tokens-in-flight 32
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

from repro.configs import ALIASES, get_config
from repro.core.communicator import CommConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.tp import ParallelCtx
from repro.models.transformer import init_params
from repro.serving.engine import (PagedServeConfig, PagedServeEngine,
                                  ServeConfig, ServeEngine)


def build_workload(rng, n_requests: int, vocab: int, max_new: int,
                   mixed: bool):
    """(prompt, max_new) pairs.  --mixed interleaves short chat-style and
    long document-style requests — the population where wave scheduling
    collapses (a long request holds the whole wave)."""
    work = []
    for i in range(n_requests):
        if mixed and i % 2 == 1:
            plen = int(rng.integers(16, 33))
            mnew = max(max_new, 16)
        else:
            plen = int(rng.integers(3, 9))
            mnew = max(4, max_new // 2) if mixed else max_new
        work.append((rng.integers(1, vocab, size=plen).tolist(), mnew))
    return work


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b", choices=sorted(ALIASES))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers at the config's own "
                         "widths (0 = the published depth)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", choices=["on", "off"], default="off",
                    help="'on': continuous batching over the paged KV "
                         "cache; 'off': the legacy wave engine (the "
                         "parity baseline)")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="tokens per paged KV block")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="pool blocks per layer (0 = auto-size, no "
                         "preemption pressure)")
    ap.add_argument("--max-tokens-in-flight", type=int, default=32,
                    help="packed-row budget per tick (top batch-shape "
                         "bucket)")
    ap.add_argument("--max-requests", type=int, default=8,
                    help="concurrent admitted requests (paged engine)")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed short/long prompt+output lengths")
    ap.add_argument("--assert-warm", action="store_true",
                    help="exit 2 unless (a) the engine re-jitted at most "
                         "one executable per batch-shape bucket per plan "
                         "(admission-driven shape changes must be "
                         "exec-cache hits) and (b) every tuned Stage-1 "
                         "slot warm-started, when communicators exist")
    ap.add_argument("--out", default="",
                    help="write the serve record (serving block + cache "
                         "stats) to this JSON path")
    ap.add_argument("--tuning-cache", default="",
                    help="TuningProfile JSON: warm-start Stage-1 shares "
                         "and persist them back when draining finishes")
    ap.add_argument("--timing", choices=["sim", "measured"], default="sim",
                    help="Stage-2 TimingSource (control/timing.py)")
    ap.add_argument("--secondary-algo", choices=["ring", "tree"],
                    default="ring")
    ap.add_argument("--compress", default="",
                    help="secondary-path wire codecs, e.g. 'secondary=fp8' "
                         "or 'staged=bf16,ortho=fp8' (DESIGN.md §12)")
    ap.add_argument("--degrade", default="",
                    help="fault injection name[:member]=factor "
                         "(DESIGN.md §10); with --nodes it degrades the "
                         "cluster's NIC tier, else the node profile")
    ap.add_argument("--fault", default="",
                    help="fault-timeline schedule over serve TICKS "
                         "(repro.faults, DESIGN.md §14), e.g. "
                         "'rail3@step50=0.25': committed transitions swap "
                         "the communicators' fabric mid-drain with warm "
                         "Stage-2 re-convergence.  Node events are not "
                         "supported here (serving has no elastic resume)")
    ap.add_argument("--nodes", type=int, default=1,
                    help="cluster node count: registers the NIC-tier "
                         "profile (so --tuning-cache keys line up with "
                         "multi-node launches) and records the topology "
                         "on the ctx.  This launcher itself is "
                         "single-device — the decode wave never crosses "
                         "the NIC tier (launch/shapes.py)")
    ap.add_argument("--pods", type=int, default=1,
                    help="pod count for the registered topology: with "
                         "--nodes > 1 the synthesized cluster grows the "
                         "pod/DCN tier (DESIGN.md §15) so tuning-cache "
                         "keys line up with 3-tier launches")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)

    # single-device ctx, but with the comm config plumbed so a multi-axis
    # deployment of this launcher inherits the control-plane flags
    from repro.configs.clusters import resolve_faults
    profile = "tpu_v5e"
    cluster = None
    if args.nodes > 1:
        from repro.cluster.topology import cluster_for
        cluster = cluster_for(profile, args.nodes, pods=max(args.pods, 1))
    cluster, profile, timeline = resolve_faults(
        cluster, args.nodes, profile,
        degrade=args.degrade, fault=args.fault, pods=max(args.pods, 1))
    if timeline is not None and any(e.kind == "node"
                                    for e in timeline.events):
        raise SystemExit("--fault node events need the training loop's "
                         "elastic resume; serving supports link/member "
                         "schedules only")
    comm = CommConfig(
        profile=profile, timing=args.timing,
        secondary_algo=args.secondary_algo,
        tuning_cache=args.tuning_cache,
        compress=args.compress,
        fault=timeline.spec() if timeline else "")
    ctx = ParallelCtx(comm_config=comm, cluster=cluster)
    clock = None
    if timeline is not None:
        from repro.faults import FabricClock
        clock = FabricClock(timeline).attach(ctx)
    if not ctx.comms() and (args.timing != "sim" or args.tuning_cache
                            or args.secondary_algo != "ring"
                            or args.nodes > 1 or args.degrade
                            or args.compress or args.fault):
        print("note: single-device launch has no communicators — "
              "--timing/--tuning-cache/--secondary-algo/--nodes/--degrade/"
              "--fault/--compress take effect only with parallel axes (the "
              "decode wave itself never crosses the NIC tier; see "
              "launch/shapes.py)")
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.paged == "on":
        engine = PagedServeEngine(params, cfg, ctx, PagedServeConfig(
            max_requests=args.max_requests, cache_len=96,
            kv_block=args.kv_block, n_blocks=args.kv_blocks,
            max_tokens_in_flight=args.max_tokens_in_flight))
    else:
        engine = ServeEngine(params, cfg, ctx,
                             ServeConfig(slots=args.slots, cache_len=96))
    rng = np.random.default_rng(0)
    t0 = time.time()
    for prompt, mnew in build_workload(rng, args.requests, cfg.vocab,
                                       args.max_new, args.mixed):
        engine.submit(prompt, max_new=mnew, temperature=args.temperature)
    engine.run_until_drained()
    dt = time.time() - t0
    fin = engine.finished()
    total_toks = sum(len(v) for v in fin.values())
    print(f"served {len(fin)} requests, {total_toks} tokens "
          f"in {dt:.1f}s ({total_toks / dt:.1f} tok/s, "
          f"engine={args.paged == 'on' and 'paged' or 'wave'})")
    rep = engine.comm_report()
    ec = rep["executable_cache"]
    print(f"decode executable cache: {ec['rebuilds']} rebuilds, "
          f"{ec['hits']} hits, {ec['evictions']} evictions")
    # issue/await lifecycle (DESIGN.md §11): every decode tick is issued
    # async and awaited, so issued == awaits and nothing stays in flight
    # past drain
    pr = rep["program"]
    print(f"decode issue/await: {pr['issued']} issued, "
          f"{pr['awaits']} awaited, {pr['in_flight']} in flight")
    assert pr["in_flight"] == 0
    srv = rep["serving"]
    if srv["engine"] == "paged":
        tif = srv["tokens_in_flight"]
        bc = srv["batch_bucket_cache"]
        kv = srv["kv_blocks"]
        print(f"serving: {srv['steps']} packed steps, tokens in flight "
              f"peak {tif['peak']}/{tif['budget']}, buckets "
              f"{srv['buckets']}, bucket-cache hit rate {bc['hit_rate']} "
              f"({bc['hits']} hits / {bc['rebuilds']} rebuilds)")
        print(f"serving: {srv['scheduler']['preemptions']} preemptions, "
              f"kv blocks peak {kv['peak_in_use']}/{kv['total']}")
        pf = srv["scheduler"]["prefill"]
        if pf["requests"]:
            print(f"serving: admission to first token p80 "
                  f"{pf['p80_ms']:.1f} ms over {pf['requests']} requests, "
                  f"no row in {100 * pf['stall_share']:.1f}% of those ticks")
        ch = srv["attn_chunks"]
        if ch["span"]:
            print(f"serving: attention ran {ch['run']} of {ch['span']} "
                  f"K/V chunks ({100 * ch['run'] / ch['span']:.1f}%)")
        if "moe" in srv:
            moe = srv["moe"]
            print(f"serving: {moe['held']} of {moe['assigned']} token-expert "
                  f"pairs on the held experts, per expert "
                  f"{moe['per_expert']}")
    if clock is not None:
        fr = clock.report()
        print(f"faults: {len(fr['transitions'])} transition(s), "
              f"{fr['rekeys']} re-key(s), {fr['suppressed_flaps']} "
              f"suppressed flap(s)")
    if args.tuning_cache:
        n = engine.save_tuning(args.tuning_cache)
        print(f"tuning profile: {n} slots -> {args.tuning_cache}")
    if args.out:
        d = os.path.dirname(args.out)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"arch": args.arch, "engine": srv["engine"],
                       "requests": len(fin), "tokens": total_toks,
                       "wall_s": round(dt, 3), "serving": srv,
                       "executable_cache": ec, "program": pr,
                       **({"faults": clock.report()} if clock else {})},
                      f, indent=2, default=str)
        print(f"serve record -> {args.out}")
    for rid in sorted(fin)[:4]:
        print(f"  req {rid}: {fin[rid][:10]}")
    assert len(fin) == args.requests

    if args.assert_warm:
        failures = []
        # (a) zero admission-driven re-jits: at most one rebuild per
        # batch-shape bucket (single-device ctx = one plan signature)
        buckets = max(len(pr.get("shape_buckets", [])), 1)
        if ec["rebuilds"] > buckets:
            failures.append(
                f"{ec['rebuilds']} rebuilds > {buckets} bucket(s): "
                "admission-driven shape changes re-jitted")
        if srv["engine"] == "paged" and ec["hits"] == 0:
            failures.append("no exec-cache hits — vacuous bucket check")
        # (b) Stage-1 warm start, when there are tuned slots
        slots = [s for ax in ctx.tuning_status().values()
                 for s in ax.values()]
        cold = [s for s in slots if not s.get("warm")]
        if cold:
            failures.append(f"{len(cold)} tuned slot(s) ran Stage-1 cold")
        if failures:
            for msg in failures:
                print(f"[FAIL] --assert-warm: {msg}")
            engine.close()
            return 2
        print(f"[OK] --assert-warm: {ec['rebuilds']} rebuilds across "
              f"{buckets} bucket(s), {len(slots)} tuned slots warm")
    engine.close()
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
