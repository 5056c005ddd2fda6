"""Readings that the limits of ``correct`` are set from, at a cell's own
size, many seeds in one process.

    python chipbench/calibrate.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 20]

For every seed it prints one JSON line with the program's numbers, read
as a run reads them (chipbench/check.py).  On the control seeds it adds
the control's numbers: the reference computed with float8 e4m3 operands
put in the program's place.  The benchmark's own runs do not run any of
this.  Needs the cell's TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import bench, check  # noqa: E402


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def serve(cell, devices, seeds, control_seeds, seconds):
    """One row of readings for each seed (see the module doc)."""
    from chipbench.drivers import serve as D
    tr = cell.traffic
    for seed in seeds:
        engine, params = D.build(cell, devices[0], seed)
        win = D.serve_window(engine, D.schedule(cell, seed, seconds), seconds,
                             drain_seconds=tr["drain_seconds"])
        engine.run_until_drained()
        engine.close()
        del engine
        finished = win.finished()
        pick = check.sample_requests(finished, tr["check"]["requests"], seed)
        reqs = [finished[r] for r in pick]
        row = {"seed": seed, "requests": len(pick),
               "served_tokens": sum(len(o) for _, o in reqs),
               "program": {"served_logit_gap":
                           check.served_gaps(cell.arch, params, reqs)}}
        if seed in control_seeds:
            row["control"] = {"served_logit_gap": check.served_gaps(
                cell.arch, params, reqs, mode="fp8", control=True)}
        del params
        gc.collect()
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload, ROOT)
    devices = bench.require_devices(cell.chips)
    bench.enable_compile_cache()
    t0 = time.perf_counter()
    seeds, control = _seeds(args.seeds), set(_seeds(args.control_seeds))
    for row in serve(cell, devices, seeds, control, args.seconds):
        print(json.dumps(row), flush=True)
    print(f"calibration took {time.perf_counter() - t0} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
