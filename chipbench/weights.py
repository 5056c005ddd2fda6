"""The benchmark's weights: made on the device from the seed, in one
jitted call, in the type the configuration serves them in.

The tree is the dense decoder's as the program stores it: layers stacked
on a leading axis.  Matrices are N(0, 0.02^2); norm weights 1 + N(0,
0.1^2), so that a norm whose weight is dropped shows.  The reference
regenerates the same arrays from the same seed with ``make``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

STD = 0.02
NORM_STD = 0.1


def shapes(a: Dict) -> Dict:
    """{leaf path: (shape, is_norm)} of the stacked dense tree."""
    d, f, L, v = a["d_model"], a["d_ff"], a["n_layers"], a["vocab"]
    hd = a.get("head_dim") or d // a["n_heads"]
    q, kv = a["n_heads"] * hd, a["n_kv_heads"] * hd
    return {
        "embed": ((v, d), False),
        "final_norm": ((d,), True),
        "lm_head": ((d, v), False),
        "layers/ln1": ((L, d), True),
        "layers/ln2": ((L, d), True),
        "layers/attn/wq": ((L, d, q), False),
        "layers/attn/wk": ((L, d, kv), False),
        "layers/attn/wv": ((L, d, kv), False),
        "layers/attn/wo": ((L, q, d), False),
        "layers/mlp/w_gate": ((L, d, f), False),
        "layers/mlp/w_up": ((L, d, f), False),
        "layers/mlp/w_down": ((L, f, d), False),
    }


def nest(flat: Dict[str, object]) -> Dict:
    out: Dict = {}
    for path, x in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make(a: Dict, seed: int, shardings=None):
    """The nested weight tree on the device.  ``shardings``: a matching
    tree of shardings, or None for the default device."""
    dtype = jnp.dtype(a.get("param_dtype", "bfloat16"))
    spec = shapes(a)

    def build(key):
        flat = {}
        for i, (path, (shape, is_norm)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if is_norm:
                x = 1.0 + NORM_STD * jax.random.normal(k, shape, jnp.float32)
            else:
                x = STD * jax.random.normal(k, shape, jnp.float32)
            flat[path] = x.astype(dtype)
        return nest(flat)

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def flatten(tree: Dict, prefix: str = "") -> Dict[str, object]:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out
