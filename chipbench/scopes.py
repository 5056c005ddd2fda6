"""The program's named scopes on the device trace's ops.

The ``XLA Ops`` events of a TPU trace carry no ``op_name``: an op is
named only by its HLO instruction text.  The scope it was traced under
(``jax.named_scope``) is in the compiled program's HLO text, as
``metadata={op_name="jit(f)/.../<scope>/.../<primitive>"}`` on each
instruction.  So the serving step is compiled again after the window, at
every bucket of the engine's ladder (a load from the persistent compile
cache that warm-up filled), and each op of the trace is looked up by its
instruction name and result shape.

Instruction names repeat across the bucket programs.  The ops of one
tick all come from one program, so each tick's ops (those that start
inside a ``bench.tick`` span) are matched to the program text that
agrees with most of them; an op whose scope still differs between
equally good texts is ``(ambiguous)``.  Ops without a scope, or that no
text names, are ``(unscoped)``.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace as T

UNSCOPED = "(unscoped)"
AMBIGUOUS = "(ambiguous)"
#: the share of own time that may go unnamed before a share reads nothing
MAX_UNNAMED = 0.1
#: components of an ``op_name`` that JAX adds for loops and calls; the
#: rest, short of the primitive, are the program's named scopes
STRUCTURAL = frozenset({"while", "body", "cond", "closed_call"})

Table = Dict[str, Tuple[str, str]]


def scope_of(op_name: str) -> str:
    """The named-scope path of an HLO ``op_name``
    (``jit(f)/while/body/closed_call/attn/attend/dot_general`` ->
    ``attn/attend``): its components short of the primitive, less those
    JAX's transformations add and those with punctuation (``jit(f)``,
    einsum specs)."""
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts if re.fullmatch(r"[A-Za-z_]\w*", p)
                    and p not in STRUCTURAL)


def _shape(rtype: str) -> str:
    """A result type without its layouts: ``bf16[8,128]{1,0:T(8,128)}`` ->
    ``bf16[8,128]``."""
    return re.sub(r"\{[^{}]*\}", "", rtype)


def _key(text: str) -> Tuple[str, str]:
    """(name, result shape) of an instruction's text."""
    name, rtype, _, _ = T._split(text)
    return name, _shape(rtype)


def scope_table(hlo_text: str) -> Table:
    """{instruction name: (named scope, result shape)} of every
    instruction of a compiled module's HLO text (``compiled.as_text()``);
    the scope is "" for an instruction without an ``op_name``."""
    table: Table = {}
    for line in hlo_text.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if not line.startswith("%") or " = " not in line:
            continue
        name, shp = _key(line)
        m = re.search(r'op_name="([^"]*)"', line)
        table[name] = (scope_of(m.group(1)) if m else "", shp)
    return table


def _lookup(tables: Sequence[Table], keys: List[Tuple[str, str]]
            ) -> List[str]:
    """The scope of each op key, by the tables that agree with most of
    the keys."""
    score = [sum(t.get(n, ("", None))[1] == shp for n, shp in keys)
             for t in tables]
    best = max(score, default=0)
    if best == 0:
        return [UNSCOPED] * len(keys)
    winners = [t for t, sc in zip(tables, score) if sc == best]
    out = []
    for n, shp in keys:
        found = {t[n][0] for t in winners if t.get(n, ("", None))[1] == shp}
        found.discard("")
        out.append(AMBIGUOUS if len(found) > 1 else
                   found.pop() if found else UNSCOPED)
    return out


def assign(trace: T.Trace, hlo_texts: Sequence[str]
           ) -> Dict[int, List[str]]:
    """{device: the named scope of each of its ops} (see module doc)."""
    tables = [scope_table(t) for t in hlo_texts]
    ticks = [s for s in trace.host if s.name == "bench.tick"]
    out: Dict[int, List[str]] = {}
    for d, dev in trace.devices.items():
        groups: Dict[int, List[int]] = defaultdict(list)
        j = 0
        for i, o in enumerate(dev.ops):
            while j < len(ticks) and ticks[j].end <= o.start:
                j += 1
            inside = j < len(ticks) and ticks[j].start <= o.start
            groups[j if inside else -1 - i].append(i)
        scopes = [UNSCOPED] * len(dev.ops)
        for idx in groups.values():
            keys = [_key(dev.ops[i].text) for i in idx]
            for i, sc in zip(idx, _lookup(tables, keys)):
                scopes[i] = sc
        out[d] = scopes
    return out


def own_times(dev: T.Device, eps: float = 1e-8) -> List[float]:
    """Each op's own time: its duration less that of the ops nested in
    it (a ``while`` loop's span holds its body's operations).  An op is
    nested only if it ends within its parent: event times are rounded,
    so an op may start a few nanoseconds before the one ahead of it
    ends without lying inside it."""
    own = [o.seconds for o in dev.ops]
    stack: List[int] = []
    for i, o in enumerate(dev.ops):
        while stack and (dev.ops[stack[-1]].end <= o.start + eps
                         or dev.ops[stack[-1]].end + eps < o.end):
            stack.pop()
        if stack:
            own[stack[-1]] -= o.seconds
        stack.append(i)
    return own


def breakdown(trace: T.Trace, scopes: Dict[int, List[str]], lo: float,
              hi: float) -> List[List]:
    """Device seconds by named scope, each op's own time (``own_times``),
    averaged over devices; ops that start in [lo, hi)."""
    acc: Dict[str, float] = defaultdict(float)
    n = max(len(trace.devices), 1)
    for d, dev in trace.devices.items():
        for o, own, sc in zip(dev.ops, own_times(dev), scopes[d]):
            if lo <= o.start < hi:
                acc[sc] += own / n
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]


def share(trace: T.Trace, scopes: Dict[int, List[str]], lo: float,
          hi: float, scope: str) -> Optional[float]:
    """Share of the device's own op time in [lo, hi) spent in ops under
    ``scope`` (any component of their path); None where ``(unscoped)``
    and ``(ambiguous)`` ops hold ``MAX_UNNAMED`` of it or more (the texts
    do not match the program that ran)."""
    acc = dict(breakdown(trace, scopes, lo, hi))
    whole = sum(acc.values())
    if acc.get(UNSCOPED, 0.0) + acc.get(AMBIGUOUS, 0.0) >= \
            MAX_UNNAMED * whole:
        return None
    under = sum(v for k, v in acc.items() if scope in k.split("/"))
    return under / whole


def step_texts(cell) -> Optional[List[str]]:
    """The compiled HLO text of the serving step the cell's engine runs,
    at every bucket of its ladder, on the default device; None for a
    program whose engine cannot lower its step apart from a running
    engine (one whose step carries no named scopes)."""
    import jax
    import jax.numpy as jnp

    from chipbench import bench, weights as W
    from repro.core.communicator import CommConfig
    from repro.models.tp import ParallelCtx
    try:
        from repro.serving.engine import PagedServeConfig, paged_step_texts
    except ImportError:
        return None
    dtype = jnp.dtype(cell.arch.get("param_dtype", "bfloat16"))
    params = W.nest({k: jax.ShapeDtypeStruct(s, dtype)
                     for k, (s, _) in W.shapes(cell.arch).items()})
    # shapes without shardings lower to the very module the engine ran,
    # so each compile is a persistent-cache hit
    return paged_step_texts(
        bench.program_config(cell),
        ParallelCtx(comm_config=CommConfig(**cell.traffic["comm"])),
        PagedServeConfig(**cell.traffic["engine"]), params)
