"""Model FLOPs of the real rows the engine processed in the window
(``chipbench/mla_moe/flops.py``: attention at the published head widths,
the routed experts by the token-expert pairs that fell on held experts,
the output head for the rows that sampled a token) over the wall time of
the window's ticks and the chip's peak."""

from chipbench.mla_moe import flops


def read(run):
    if "moe_held" not in run.data:
        return None
    done = flops.serve_flops(run.cell.config, run.data["positions"],
                             run.data["sampled"], run.data["moe_held"])
    spent = run.data["tick_s"]
    return 100.0 * done / (spent * run.chips *
                           run.peak["bf16_flops_per_s"]) if spent else None
