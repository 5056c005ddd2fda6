"""Readings that the limit of ``correct`` is set from, for a serving cell
of the MLA + MoE tree, at the cell's own size, many seeds in one process
(``chipbench/calibrate.py`` builds the dense tree).

    python chipbench/mla_moe/calibrate.py --workload <name> --seeds 1,2 \\
        [--control-seeds 1,2] [--bf16-seeds 1] [--seconds 20] [--dump DIR]

For every seed it prints one JSON line with the program's
``served_logit_gap``, read as a run reads it, and whether it is within
the cell's limit (``correct``); on the control seeds also the control's:
the reference with float8 e4m3 operands put in the program's place; on
the ``--bf16-seeds`` the same for the reference in bfloat16, a sound
implementation independent of the program.  Besides: each reading at
every margin of ``check.MARGINS`` (how many served positions are settled
there), the widest gap of positions before and from ``SHORT`` in the
sequence, and where the lower-precision references chose other held
experts than the float32 one, the largest float32 margin at which they
did.  ``--dump`` writes every served position's numbers, one JSON file a
seed.  Needs the cell's TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import bench, check as C  # noqa: E402


SHORT = 2560          # the dense chat cell's cache length


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--bf16-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--dump", default="")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload, ROOT)
    devices = bench.require_devices(cell.chips)
    bench.enable_compile_cache()
    import jax
    from chipbench.drivers import serve as D
    from chipbench.drivers import serve_mla_moe as M
    from chipbench.mla_moe import check
    cell = M.with_arch(cell)
    tr, a = cell.traffic, cell.config
    control = set(_seeds(args.control_seeds))
    bf16 = set(_seeds(args.bf16_seeds))
    margin = tr["check"]["route_margin"]
    t0 = time.perf_counter()
    for seed in _seeds(args.seeds):
        engine, params = M.build(cell, devices[0], seed)
        with jax.default_device(devices[0]):
            win = D.serve_window(engine, D.schedule(cell, seed, args.seconds),
                                 args.seconds,
                                 drain_seconds=tr["drain_seconds"])
            M.finish(engine, win, tr["check"]["requests"],
                     tr["drain_seconds"])
        engine.close()
        del engine
        finished = win.finished()
        pick = C.sample_requests(finished, tr["check"]["requests"], seed)
        reqs = [finished[r] for r in pick]
        modes = [m for m, on in (("fp8", seed in control),
                                 ("bf16", seed in bf16)) if on]
        rows = check.served_positions(a, params, reqs, modes)
        keys = {"program": "gap", **{m: f"gap.{m}" for m in modes}}
        row = {"seed": seed, "requests": len(pick),
               "served_tokens": sum(len(o) for _, o in reqs)}
        for name, key in keys.items():
            ok, compared = C.compared(
                {"served_logit_gap": check.widest(rows, margin, key)},
                tr["limits"])
            row[name] = {"served_logit_gap":
                         compared["served_logit_gap"]["value"],
                         "correct": ok}
        row["by_margin"] = [
            {"margin": m, "settled": sum(int((r["margin"] >= m).sum())
                                         for r in rows),
             **{name: check.widest(rows, m, key)
                for name, key in keys.items()}} for m in check.MARGINS]
        for part, sel in (("short", lambda p: p < SHORT),
                          ("long", lambda p: p >= SHORT)):
            row[part] = {
                "served": sum(int(sel(r["position"]).sum()) for r in rows),
                **{f"{name}@{m}": max(
                    [float(r[key][sel(r["position"]) &
                                  (r["margin"] >= m)].max())
                     for r in rows
                     if (sel(r["position"]) & (r["margin"] >= m)).any()],
                    default=0.0)
                   for name, key in keys.items() for m in (0.0, margin)}}
        row["flips"] = {m: {"count": sum(r[f"flips.{m}"] for r in rows),
                            "largest_margin": max(r[f"flip_margin.{m}"]
                                                  for r in rows)}
                        for m in modes}
        print(json.dumps(row), flush=True)
        if args.dump:
            out = Path(args.dump)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"{seed}.json").write_text(json.dumps(
                [{k: (v.tolist() if hasattr(v, "tolist") else v)
                  for k, v in r.items()} for r in rows]))
        del params
        gc.collect()
    print(f"calibration took {time.perf_counter() - t0} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
