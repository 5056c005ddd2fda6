"""Share of the chip's own op time in the traced window spent in the
expert layers: ops under the model step's ``moe`` scope (``route``, the
held experts over every row, ``experts``, and the shared expert,
``shared``; ``chipbench/mla_moe/scopes.py``).  None where a tenth or
more of the own time has no scope or an ambiguous one."""

from chipbench.mla_moe import scopes


def read(run):
    return scopes.share(run, ("moe",))
