"""Find the highest arrival rate a serving cell sustains: one engine, one
open-loop window per rate, in one process.

    python chipbench/sweep.py --workload <name> --seed <n> \\
        --rates 2,3,4,5 [--seconds 30]

For each rate it prints one JSON line: requests due, the 50th and 90th
percentile time to first token, the 95th percentile gap between tokens,
and the backlog (requests due that have no first token yet) at the end
of the window with its least-squares slope over the window's second
half.  The last line names the knee, the highest rate up to which every
backlog grew by less than ``GROWTH`` of the requests offered, and
four fifths of it, the rate a cell below the knee offers.  The cell's
traffic file keeps the rate chosen from it.  Needs the cell's TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import bench  # noqa: E402

GROWTH = 0.05


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload, ROOT)
    devices = bench.require_devices(cell.chips)
    bench.enable_compile_cache()
    from chipbench.drivers import serve as D
    engine, _ = D.build(cell, devices[0], args.seed)
    knee, below = None, True
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = D.schedule(cell, args.seed + i, args.seconds, rate=rate)
        win = D.serve_window(engine, reqs, args.seconds, drain_seconds=0.0)
        lat = D.latencies(win)
        t, b = (np.asarray(x, float) for x in zip(*win.backlog))
        late = t >= args.seconds / 2
        slope = float(np.polyfit(t[late], b[late], 1)[0])
        print(json.dumps({
            "rate": rate, "due": len(win.tracked),
            "ttft_p50_ms": float(np.percentile(lat["ttft"], 50)) * 1e3,
            "ttft_p90_ms": float(np.percentile(lat["ttft"], 90)) * 1e3,
            "itl_p95_ms": float(np.percentile(lat["itl"], 95)) * 1e3,
            "backlog_last": int(b[-1]), "backlog_slope_per_s": slope,
            "late_max_s": max(lat["late"])}), flush=True)
        below = below and slope < GROWTH * rate
        if below:
            knee = rate
        engine.run_until_drained(max_ticks=100000)
    engine.close()
    print(json.dumps({"knee": knee,
                      "rate": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
