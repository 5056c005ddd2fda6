"""What decides ``correct`` for the MLA + MoE tree: the served tokens'
logits against the plain reference (``reference.py``), computed after
the window on the same weights, as ``chipbench/check.py`` does for the
dense tree.

``served_logit_gap``: the widest gap by which a served (greedy) token's
reference logit lies below the reference's best at its position, over the
sampled requests' served positions whose routing is settled.  A position
is unsettled where, in some expert layer, a held expert lies within
``margin`` (the cell's ``check.route_margin``) of the top-k's edge of the
float32 reference's ``scores + bias`` (``reference.held_routing``): there
the rounding of any bfloat16 implementation can move that expert in or
out and shift the position's output by a share of that expert's output,
so neither served token is wrong.  The reference decides which positions
are settled; the program has no say in it.  The reference runs each
prompt with its served tokens once, layer by layer, queries in blocks of
``QUERY_BLOCK``; sequences are padded to a multiple of ``PAD`` positions
(causal: the padding changes no real position), so that few shapes
compile.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.mla_moe import reference as R

QUERY_BLOCK = 256
PAD = 2048
# routing margins the calibration reads each gap at
MARGINS = (0.0, 1e-4, 2e-4, 5e-4, 1e-3, 2e-3, 5e-3, 1e-2)


def _key(a: Dict) -> str:
    return json.dumps(a, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jitted(key: str, mode: str):
    a = json.loads(key)

    def layer(x, stacked, i, dense):
        lp = jax.tree.map(lambda t: t[i], stacked)
        return R.layer(x, lp, a, mode, dense, QUERY_BLOCK,
                       routing=not dense)

    def head(params, x, pos):
        """Final norm and output head at ``pos``, the head's columns in
        four blocks so that its float32 copy stays small."""
        h = R.rms_norm(x[pos], params["final_norm"], a["rms_norm_eps"])
        w = params["lm_head"]
        blocks = 4 if w.shape[1] % 4 == 0 else 1
        ws = w.reshape(w.shape[0], blocks, -1).transpose(1, 0, 2)
        out = jax.lax.map(lambda wb: R.matmul(h, wb, mode), ws)
        return out.transpose(1, 0, 2).reshape(h.shape[0], -1)

    return {"embed": jax.jit(R.embed),
            "dense": jax.jit(functools.partial(layer, dense=True)),
            "moe": jax.jit(functools.partial(layer, dense=False)),
            "head": jax.jit(head)}


def reference_pass(a: Dict, params, seq: Sequence[int],
                   positions: Sequence[int], mode: str, pad: int = PAD
                   ) -> Dict[str, np.ndarray]:
    """One sequence through the reference in ``mode``: ``logits`` [len(
    positions), V] at the given positions (each predicts the token after
    it), and for every real position ``margin`` [L, n] and ``chosen`` [L,
    n, held] of each expert layer (``reference.held_routing``)."""
    n = len(seq)
    tokens = np.zeros(-(-n // pad) * pad, np.int32)
    tokens[:n] = seq
    fns = _jitted(_key(a), mode)
    x = fns["embed"](params, jnp.asarray(tokens))
    margins, chosen = [], []
    for group, kind in (("prefix", "dense"), ("layers", "moe")):
        if group in params:
            for i in range(jax.tree.leaves(params[group])[0].shape[0]):
                x = fns[kind](x, params[group], i)
                if kind == "moe":
                    x, m, c = x
                    margins.append(np.asarray(m)[:n])
                    chosen.append(np.asarray(c)[:n])
    pos = np.full(-(-len(positions) // 128) * 128, positions[-1], np.int32)
    pos[:len(positions)] = positions
    out = np.asarray(fns["head"](params, x, jnp.asarray(pos)))
    return {"logits": out[:len(positions)], "margin": np.stack(margins),
            "chosen": np.stack(chosen)}


def served_positions(a: Dict, params,
                     requests: Sequence[Tuple[List[int], List[int]]],
                     modes: Sequence[str] = (), pad: int = PAD
                     ) -> List[Dict[str, np.ndarray]]:
    """Per request (prompt, served), over its served positions:
    ``position``, ``margin`` (the float32 reference's least held-expert
    margin over the expert layers) and ``gap`` (the served token's); for
    each of ``modes``, ``gap.<mode>``: the gap of the token the reference
    in that mode ranks first, and ``flip_margin.<mode>``: the largest
    float32 margin, over every position of the sequence and every expert
    layer, at which that mode chose other held experts (-1 if none), with
    ``flips.<mode>`` the count of such (position, layer) pairs."""
    out = []
    for prompt, served in requests:
        seq = list(prompt) + list(served)
        positions = list(range(len(prompt) - 1, len(seq) - 1))
        ref = reference_pass(a, params, seq, positions, "f32", pad)
        logits = ref["logits"]
        best = logits.max(-1)
        rows = np.arange(len(positions))
        row = {"position": np.asarray(positions),
               "margin": ref["margin"][:, positions].min(0),
               "gap": best - logits[rows, np.asarray(served)]}
        for mode in modes:
            low = reference_pass(a, params, seq, positions, mode, pad)
            row[f"gap.{mode}"] = best - logits[rows, low["logits"].argmax(-1)]
            flip = (low["chosen"] != ref["chosen"]).any(-1)       # [L, n]
            row[f"flips.{mode}"] = int(flip.sum())
            row[f"flip_margin.{mode}"] = float(
                ref["margin"][flip].max()) if flip.any() else -1.0
        out.append(row)
    return out


def widest(rows: Sequence[Dict[str, np.ndarray]], margin: float,
           key: str = "gap") -> float:
    """The widest ``key`` gap over the settled positions (margin at least
    ``margin``) of ``rows`` (:func:`served_positions`)."""
    gaps = [r[key][r["margin"] >= margin] for r in rows]
    return max((float(g.max()) for g in gaps if g.size), default=0.0)
