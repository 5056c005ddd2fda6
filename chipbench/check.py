"""What decides ``correct``: the program's outputs against the plain
reference (``reference.py``), computed after the window on the same
weights, which the benchmark made from the seed.

Serving: ``served_logit_gap`` - the widest gap by which a served token's
reference logit lies below the reference's best at that position, over a
sample of finished requests drawn from the seed, the longest among them.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as R

F32 = jnp.float32


def compared(values: Dict[str, float], limits: Dict[str, float]
             ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(every value within its limit, {name: {value, limit}})."""
    out = {k: {"value": float(values[k]), "limit": float(limits[k])}
           for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in out.values())
    return ok, out


# -- serving -------------------------------------------------------------------

QUERY_BLOCK = 512


def _layer_fn(a: Dict, mode: str):
    """One layer of the reference over one sequence [1, S, D], attention
    taken in blocks of queries so that the scores fit beside the
    weights."""
    def fn(x, layers, i):
        lp = jax.tree.map(lambda t: t[i], layers)
        return R.layer(x, lp, a, mode, QUERY_BLOCK)
    return jax.jit(fn)


def reference_served_logits(a: Dict, params, seq: Sequence[int],
                            positions: Sequence[int], mode: str
                            ) -> np.ndarray:
    """Reference logits [len(positions), V] of one sequence at the given
    positions (each predicts the token after it), layer by layer."""
    n = len(seq)
    s = -(-n // QUERY_BLOCK) * QUERY_BLOCK
    tokens = np.zeros((1, s), np.int32)
    tokens[0, :n] = seq
    fns = _fns(a, mode)
    x = fns["embed"](params["embed"], jnp.asarray(tokens))
    for i in range(a["n_layers"]):
        x = fns["layer"](x, params["layers"], i)
    # positions padded to a multiple of 128, so that few shapes compile
    pos = np.full(-(-len(positions) // 128) * 128, positions[-1], np.int32)
    pos[:len(positions)] = positions
    out = np.asarray(fns["head"](params, x, jnp.asarray(pos)))
    return out[:len(positions)]


def _fns(a: Dict, mode: str):
    return _jitted(tuple(sorted(a.items())), mode)


@functools.lru_cache(maxsize=None)
def _jitted(arch_items, mode: str):
    a = dict(arch_items)

    def head(params, x, pos):
        """Final norm and output head at ``pos``, the head's columns in
        four blocks so that its float32 copy stays small."""
        h = R.rms_norm(x[0, pos], params["final_norm"], a["norm_eps"])
        w = params["lm_head"]
        blocks = 4 if w.shape[1] % 4 == 0 else 1
        ws = w.reshape(w.shape[0], blocks, -1).transpose(1, 0, 2)
        out = jax.lax.map(lambda wb: R.matmul(h, wb, mode), ws)
        return out.transpose(1, 0, 2).reshape(h.shape[0], -1)

    return {"embed": jax.jit(lambda e, t: e[t].astype(F32)),
            "layer": _layer_fn(a, mode),
            "head": jax.jit(head)}


def served_gaps(a: Dict, params, requests: Sequence[Tuple[List[int],
                                                            List[int]]],
                mode: str = "f32", control: bool = False) -> float:
    """The widest served-token gap over ``requests`` (prompt, served).

    With ``control`` the reference in ``mode`` stands in the program's
    place: at each served position the token it ranks first is read
    against the float32 reference instead of the served token."""
    worst = 0.0
    for prompt, out in requests:
        seq = list(prompt) + list(out)
        positions = list(range(len(prompt) - 1, len(seq) - 1))
        ref = reference_served_logits(a, params, seq, positions, "f32")
        if control:
            low = reference_served_logits(a, params, seq, positions, mode)
            picked = low.argmax(-1)
        else:
            picked = np.asarray(out)
        gap = ref.max(-1) - ref[np.arange(len(positions)), picked]
        worst = max(worst, float(gap.max()))
    return worst


def sample_requests(finished: Dict[int, Tuple[List[int], List[int]]],
                    k: int, seed: int) -> List[int]:
    """``k`` finished request ids drawn from the seed, the longest
    (prompt plus served tokens) among them."""
    ids = sorted(finished)
    if not ids:
        return []
    longest = max(ids, key=lambda r: (len(finished[r][0]) +
                                      len(finished[r][1]), r))
    rest = [r for r in ids if r != longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]
