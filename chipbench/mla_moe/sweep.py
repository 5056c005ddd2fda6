"""Find the highest arrival rate a serving cell of the MLA + MoE tree
sustains: one engine, one open-loop window per rate, rates in rising
order, in one process (``chipbench/sweep.py`` builds the dense tree).

    python chipbench/mla_moe/sweep.py --workload <name> --seed <n> \\
        --rates 0.1,0.2,0.3 [--seconds 45]

For each rate it prints one JSON line: requests due, the 50th and 90th
percentile time to first token, the 95th percentile gap between tokens,
the ticks, their mean wall time and real rows, and the backlog (requests
due that have no first token yet) at the end of the window with its
least-squares slope over the window's second half.  Between rates the
engine runs on until every request due has its first token; requests
still generating carry into the next window, as they would at a steady
load.  It stops after the first rate whose backlog grew by ``GROWTH`` of
the requests offered or more, and names the knee, the highest rate
below it, and four fifths of it.  Needs the cell's TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import bench  # noqa: E402

GROWTH = 0.05


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload, ROOT)
    devices = bench.require_devices(cell.chips)
    bench.enable_compile_cache()
    import jax
    from chipbench.drivers import serve as D
    from chipbench.drivers import serve_mla_moe as M
    cell = M.with_arch(cell)
    engine, _ = M.build(cell, devices[0], args.seed)
    knee = None
    with jax.default_device(devices[0]):
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            steps = engine.serving_report()["steps"]
            reqs = D.schedule(cell, args.seed + i, args.seconds, rate=rate)
            win = D.serve_window(engine, reqs, args.seconds,
                                 drain_seconds=3 * args.seconds)
            ticks = engine.serving_report()["steps"] - steps
            lat = D.latencies(win)
            t, b = (np.asarray(x, float) for x in zip(*win.backlog))
            late = t >= args.seconds / 2
            slope = float(np.polyfit(t[late], b[late], 1)[0])
            print(json.dumps({
                "rate": rate, "due": len(win.tracked),
                "ttft_p50_ms": float(np.percentile(lat["ttft"], 50)) * 1e3,
                "ttft_p90_ms": float(np.percentile(lat["ttft"], 90)) * 1e3,
                "itl_p95_ms": float(np.percentile(lat["itl"], 95)) * 1e3,
                "ticks": ticks, "tick_ms": 1e3 * win.tick_s / max(ticks, 1),
                "rows_per_tick": win.rows[0] / max(ticks, 1),
                "backlog_last": int(b[-1]), "backlog_slope_per_s": slope,
                "late_max_s": max(lat["late"])}), flush=True)
            if slope >= GROWTH * rate:
                break
            knee = rate
    engine.close()
    print(json.dumps({"knee": knee,
                      "rate": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
