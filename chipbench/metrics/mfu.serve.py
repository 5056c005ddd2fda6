"""Model FLOPs of the real rows the engine processed in the window (each
at its own position, the output head for the rows that sampled a token;
chipbench/flops.py) over the wall time of the window's ticks and the
chip's peak: the whole model step's share of the peak, which rises when
a tick gets faster, whatever the load offered."""

from chipbench import flops


def read(run):
    done = flops.serve_flops(run.cell.arch, run.data["positions"],
                             run.data["sampled"])
    spent = run.data["tick_s"]
    return 100.0 * done / (spent * run.chips *
                           run.peak["bf16_flops_per_s"]) if spent else None
