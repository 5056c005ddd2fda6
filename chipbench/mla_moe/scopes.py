"""Device time by the model step's named scopes for the MLA + MoE tree:
``chipbench/scopes.py``'s matching of trace ops to the step's compiled
HLO text, with the step texts of this tree (``scopes.step_texts`` builds
the dense tree)."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from chipbench import scopes as S

_TEXTS: Dict[str, List[str]] = {}
_OWN: Dict[int, Tuple] = {}


def step_texts(cell) -> Optional[List[str]]:
    """The compiled HLO text of the serving step the cell's engine runs,
    at every bucket of its ladder, on the default device (loads from the
    persistent compile cache that warm-up filled); None for a program
    that cannot build this tree."""
    if cell.name in _TEXTS:
        return _TEXTS[cell.name]
    from repro.core.communicator import CommConfig
    from repro.models.tp import ParallelCtx
    from repro.serving.engine import PagedServeConfig, paged_step_texts

    from chipbench.drivers.serve_mla_moe import program_config
    from chipbench.mla_moe import weights as W
    a = cell.config
    try:
        cfg = program_config(a)
    except (ImportError, TypeError):
        return None
    _TEXTS[cell.name] = paged_step_texts(
        cfg, ParallelCtx(comm_config=CommConfig(**cell.traffic["comm"])),
        PagedServeConfig(**cell.traffic["engine"]), W.abstract(a))
    return _TEXTS[cell.name]


def own_times(run) -> Optional[Dict[str, float]]:
    """Own op time in the traced window by scope path, the trace matched
    to the step's texts once per run (every reader of the run shares
    it); None without a trace or step texts."""
    hit = _OWN.get(id(run))
    if hit is None or hit[0] is not run:
        texts = run.trace and step_texts(run.cell)
        acc = dict(S.breakdown(run.trace, S.assign(run.trace, texts),
                               *run.trace_window)) if texts else None
        hit = _OWN[id(run)] = (run, acc)
    return hit[1]


def share(run, names) -> Optional[float]:
    """Percent of the chip's own op time in the traced window spent in
    ops under any of the scopes ``names`` (any component of their path);
    None without a trace, or where ``(unscoped)`` and ``(ambiguous)`` ops
    hold ``scopes.MAX_UNNAMED`` of it or more."""
    acc = own_times(run)
    if not acc:
        return None
    whole = sum(acc.values())
    if not whole or acc.get(S.UNSCOPED, 0.0) + acc.get(S.AMBIGUOUS, 0.0) \
            >= S.MAX_UNNAMED * whole:
        return None
    under = sum(v for k, v in acc.items() if set(k.split("/")) & set(names))
    return 100.0 * under / whole
