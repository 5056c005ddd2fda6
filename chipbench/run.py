"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

One process drives all of the cell's chips.  It makes the weights and
inputs on the device from ``--seed``, warms up the cell's own shapes,
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON line: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``.
It exits non-zero, and prints no result, without TPUs.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import bench  # noqa: E402

T_START = bench.process_start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--shape-seed", type=int, default=None,
                    help="draw the arrival schedule from this seed instead "
                         "of the traffic file's (held-out readings)")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload, ROOT)
    if args.shape_seed is not None:
        cell.traffic["arrivals"]["shape_seed"] = args.shape_seed
    devices = bench.require_devices(cell.chips)
    bench.enable_compile_cache()
    out = bench.driver(cell).run(cell, devices, seed=args.seed,
                                 seconds=args.seconds, trace=bool(args.trace),
                                 t_start=T_START)
    bench.print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
