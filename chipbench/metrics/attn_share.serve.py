"""Share of the chip's own op time in the traced window spent in ops
under the model step's ``attn`` scope: the paged K/V write, gather and
attention math, not the projections around them (``chipbench/scopes.py``
names each op's scope from the step's compiled HLO text).  None where
a tenth or more of the own time has no scope or an ambiguous one."""

from chipbench import scopes as S


def read(run):
    texts = run.trace and S.step_texts(run.cell)
    if not texts:
        return None
    share = S.share(run.trace, S.assign(run.trace, texts),
                    *run.trace_window, "attn")
    return None if share is None else 100.0 * share
