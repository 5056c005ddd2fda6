"""Serving cells of the latent-attention, sigmoid-routed expert tree
(Kimi K2): ``PagedServeEngine.tick`` under open-loop traffic on one
device, as ``drivers/serve.py`` runs the dense tree, with this tree's
program configuration, weights, check and counters.

The configuration file holds the published config's keys
(``configs/kimi-k2.json``), with ``experts_first`` and ``experts_held``
naming the routed experts this chip holds.  The program's configuration
is the registered model's with the file's depth, vocabulary and held
experts; every other setting of the file must equal the registered one,
else the run stops before it makes a weight.

Besides ``drivers/serve.py``'s data the run hands the readers
``moe_assigned`` and ``moe_held``: the token-expert pairs the window's
real rows routed, and those that fell on held experts
(``serving_report()["moe"]`` at the window's close less at its open).
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict

import jax
import numpy as np

from chipbench import bench, check as C, traffic as TR
from chipbench.drivers import serve as D
from chipbench.mla_moe import check, weights as W


def program_config(a: Dict):
    """The program's ArchConfig for the settings ``a``: the registered
    ``a["model"]`` with every setting of the file (raises on a program
    that has no latent attention or held experts)."""
    from repro.configs import get_config
    from repro.models.config import MLAConfig, YarnConfig
    base = get_config(a["model"])
    y = a["rope_scaling"]
    cfg = dataclasses.replace(
        base, n_layers=a["num_hidden_layers"], d_model=a["hidden_size"],
        n_heads=a["num_attention_heads"],
        n_kv_heads=a["num_key_value_heads"], d_ff=a["intermediate_size"],
        vocab=a["vocab_size"], head_dim=0, qkv_bias=a["attention_bias"],
        rope_theta=float(a["rope_theta"]), norm_eps=a["rms_norm_eps"],
        tie_embeddings=a["tie_word_embeddings"],
        param_dtype=a.get("param_dtype", "bfloat16"),
        mla=MLAConfig(q_lora_rank=a["q_lora_rank"],
                      kv_lora_rank=a["kv_lora_rank"],
                      qk_nope_head_dim=a["qk_nope_head_dim"],
                      qk_rope_head_dim=a["qk_rope_head_dim"],
                      v_head_dim=a["v_head_dim"]),
        rope_scaling=YarnConfig(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        moe=dataclasses.replace(
            base.moe, n_experts=a["n_routed_experts"],
            top_k=a["num_experts_per_tok"],
            n_dense_prefix=a["first_k_dense_replace"],
            d_expert=a["moe_intermediate_size"],
            n_shared=a["n_shared_experts"], scoring=a["scoring_func"],
            routed_scale=a["routed_scaling_factor"],
            held=(a["experts_first"], a["experts_held"])))
    cfg.validate()
    return cfg


def check_published(a: Dict) -> None:
    """Stop unless the program's registered model has every setting of
    the file but the cut ones (depth, vocabulary, experts held)."""
    from repro.configs import get_config
    base = get_config(a["model"])
    cut = program_config(a)
    want = dataclasses.replace(cut, n_layers=base.n_layers, vocab=base.vocab,
                               param_dtype=base.param_dtype,
                               moe=dataclasses.replace(cut.moe, held=None))
    if want != base:
        raise SystemExit(f"the program's {a['model']} differs from the "
                         f"configuration file: {base} against {want}")


def with_arch(cell: bench.Cell) -> bench.Cell:
    """The cell with the ``arch`` block ``drivers/serve.py`` reads the
    vocabulary from."""
    return dataclasses.replace(
        cell, config={**cell.config,
                      "arch": {"vocab": cell.config["vocab_size"]}})


def build(cell: bench.Cell, device, seed: int):
    """The seed's weights and a warmed-up engine on ``device``."""
    from repro.core.communicator import CommConfig
    from repro.models.tp import ParallelCtx
    from repro.serving.engine import PagedServeConfig, PagedServeEngine
    a = cell.config
    check_published(a)
    cfg = program_config(a)
    with jax.default_device(device):
        params = W.make(a, seed)
        ctx = ParallelCtx(comm_config=CommConfig(**cell.traffic["comm"]))
        engine = PagedServeEngine(params, cfg, ctx,
                                  PagedServeConfig(**cell.traffic["engine"]))
        for prompt in TR.warmup_prompts(seed, engine.buckets,
                                        a["vocab_size"]):
            engine.submit(prompt, max_new=1)
            engine.run_until_drained()
    return engine, params


def window(engine, reqs, seconds: float, **kw):
    """``drivers/serve.py``'s window, and the engine's counts over it:
    the window reads ``serving_report()`` as it opens and as it closes,
    and has no hook at its close, so the engine's method is wrapped for
    the window's span.  Returns (window, MoE pairs ``assigned`` and
    ``held`` in it, engine ticks in it)."""
    reports = []
    real = engine.serving_report

    def report():
        rep = real()
        reports.append(rep)
        return rep
    engine.serving_report = report
    try:
        win = D.serve_window(engine, reqs, seconds, **kw)
    finally:
        engine.serving_report = real
    opened, closed = reports[0], reports[1]
    moe = {k: closed["moe"][k] - opened["moe"][k]
           for k in ("assigned", "held")}
    return win, moe, closed["ticks"] - opened["ticks"]


def in_flight_peak(win) -> int:
    """The most requests between admission and their last token at once,
    in the window and its drain."""
    edges = sorted(e for t in win.tracked.values()
                   if t.admitted is not None and t.tokens
                   for e in ((t.admitted, 1), (t.tokens[-1], -1)))
    now = peak = 0
    for _, d in edges:
        now += d
        peak = max(peak, now)
    return peak


def finish(engine, win, k: int, seconds: float) -> None:
    """Tick on after the window, taking no new requests, until ``k`` of
    its requests have finished or for at most ``seconds``: the check
    compares finished requests, and at this tree's lengths (a few
    thousand prompt tokens, a few hundred out) few finish inside the
    window."""
    t0 = time.perf_counter()
    while (len(win.finished()) < k and engine.sched.has_work()
           and time.perf_counter() - t0 < seconds):
        engine.tick()


def run(cell: bench.Cell, devices, *, seed: int, seconds: float,
        trace: bool, t_start: float) -> Dict:
    cell = with_arch(cell)
    tr = cell.traffic
    run = bench.Run(cell, seed, seconds, bench.peaks(devices[0].device_kind),
                    len(devices))
    engine, params = build(cell, devices[0], seed)
    reqs = D.schedule(cell, seed, seconds)
    tracer = bench.Tracer(tr["trace_seconds"]) if trace else None

    def opened(t0):
        run.setup_s = t0 - t_start

    with jax.default_device(devices[0]):
        win, moe, ticks = window(engine, reqs, seconds, tracer=tracer,
                                 drain_seconds=tr["drain_seconds"],
                                 on_open=opened)
        finish(engine, win, tr["check"]["requests"], tr["drain_seconds"])
    run.window_s = win.end
    mem = bench.memory_peak(devices)
    blocks = engine.serving_report()["kv_blocks"]
    print(f"occupancy: {win.rows[0] / max(ticks, 1)} real rows a tick over "
          f"{ticks} ticks in the window; at most {in_flight_peak(win)} "
          f"requests in flight; pool blocks in use at peak "
          f"{blocks['peak_in_use']} of {engine.kv.allocator.n_blocks}",
          file=sys.stderr)
    engine.close()
    del engine

    lat = D.latencies(win)
    print(f"generator lateness: median {np.median(lat['late'])} s, max "
          f"{max(lat['late'])} s over {len(lat['late'])} requests",
          file=sys.stderr)
    run.data.update(ttft=lat["ttft"], itl=lat["itl"],
                    admit_wait=lat["admit_wait"], positions=win.positions,
                    sampled=win.sampled, tick_s=win.tick_s,
                    rows_real=win.rows[0], rows_padded=win.rows[1],
                    moe_assigned=moe["assigned"], moe_held=moe["held"])
    if tracer:
        run.trace = tracer.reduce()
        if run.trace is not None:
            from chipbench import trace as T
            run.trace_window = T.window(run.trace, "bench.window")
    failed = len(win.tracked) - len(lat["ttft"])

    finished = win.finished()
    pick = C.sample_requests(finished, tr["check"]["requests"], seed)
    margin = tr["check"]["route_margin"]
    rows = check.served_positions(cell.config, params,
                                  [finished[r] for r in pick])
    gap = check.widest(rows, margin)
    settled = sum(int((r["margin"] >= margin).sum()) for r in rows)
    print(f"compared {settled} settled of "
          f"{sum(len(r['gap']) for r in rows)} served tokens of {len(pick)} "
          f"requests (widest gap {check.widest(rows, -1.0)} over all); "
          f"{moe['held']} of {moe['assigned']} token-expert pairs on held "
          f"experts", file=sys.stderr)
    print("widest gap by routing margin: " + ", ".join(
        f"{m}: {check.widest(rows, m)}" for m in check.MARGINS),
        file=sys.stderr)
    ok, compared = C.compared({"served_logit_gap": gap}, tr["limits"])
    return bench.result_line(run, trace=trace,
                             correct=ok and settled > 0 and failed == 0,
                             attempted=len(win.tracked), failed=failed,
                             compared=compared, devices=devices,
                             memory_peak_bytes=mem)
