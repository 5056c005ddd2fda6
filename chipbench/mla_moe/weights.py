"""The benchmark's weights for the MLA + MoE tree, made on the device from
the seed, one jitted call per leaf, in the type the configuration serves
them in.

The tree is the program's: the dense prefix layers stacked under
``prefix``, the expert layers under ``layers``, each expert layer holding
the router over every routed expert, the correction bias, the experts
this chip holds and the shared expert.  Matrices are N(0, 0.02^2); norm
weights 1 + N(0, 0.1^2), so that a norm whose weight is dropped shows.
The correction bias is float32, N(0, ``BIAS_STD^2``): at that scale it
changes some of every token's top-8 (``tests/test_mla_moe.py``), so a
program that leaves it out fails the comparison.

``a`` is a configuration file's settings (``configs/kimi-k2.json``): the
published config's keys, with ``experts_first`` and ``experts_held``
naming the experts held here.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.weights import NORM_STD, STD, flatten, nest, seed_key  # noqa

BIAS_STD = 0.02


def shapes(a: Dict) -> Dict:
    """{leaf path: (shape, kind)}, kind "matrix", "norm" or "bias"."""
    d, v = a["hidden_size"], a["vocab_size"]
    h, r = a["num_attention_heads"], a["kv_lora_rank"]
    nope, rope, vd = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
    qr, e, fe = a["q_lora_rank"], a["n_routed_experts"], \
        a["moe_intermediate_size"]
    npre = a["first_k_dense_replace"]
    nmoe = a["num_hidden_layers"] - npre
    held = a["experts_held"]
    fs = a["n_shared_experts"] * fe

    def attn(n, prefix):
        return {
            f"{prefix}/ln1": ((n, d), "norm"),
            f"{prefix}/ln2": ((n, d), "norm"),
            f"{prefix}/attn/wq_a": ((n, d, qr), "matrix"),
            f"{prefix}/attn/q_norm": ((n, qr), "norm"),
            f"{prefix}/attn/wq_b": ((n, qr, h * (nope + rope)), "matrix"),
            f"{prefix}/attn/wkv_a": ((n, d, r + rope), "matrix"),
            f"{prefix}/attn/kv_norm": ((n, r), "norm"),
            f"{prefix}/attn/wkv_b": ((n, r, h * (nope + vd)), "matrix"),
            f"{prefix}/attn/wo": ((n, h * vd, d), "matrix"),
        }

    def ffn(n, prefix, f, lead=()):
        return {f"{prefix}/w_gate": ((n,) + lead + (d, f), "matrix"),
                f"{prefix}/w_up": ((n,) + lead + (d, f), "matrix"),
                f"{prefix}/w_down": ((n,) + lead + (f, d), "matrix")}

    out = {"embed": ((v, d), "matrix"), "final_norm": ((d,), "norm"),
           "lm_head": ((d, v), "matrix")}
    if npre:
        out.update(attn(npre, "prefix"))
        out.update(ffn(npre, "prefix/mlp", a["intermediate_size"]))
    out.update(attn(nmoe, "layers"))
    out.update({"layers/moe/w_router": ((nmoe, d, e), "matrix"),
                "layers/moe/router_bias": ((nmoe, e), "bias")})
    out.update(ffn(nmoe, "layers/moe/experts", fe, (held,)))
    if fs:
        out.update(ffn(nmoe, "layers/moe/shared", fs))
    return out


def dtype_of(a: Dict, kind: str):
    return jnp.float32 if kind == "bias" else \
        jnp.dtype(a.get("param_dtype", "bfloat16"))


def make(a: Dict, seed: int):
    """The nested weight tree on the default device."""
    key = seed_key(seed)
    flat = {}
    for i, (path, (shape, kind)) in enumerate(sorted(shapes(a).items())):
        flat[path] = _leaf(jax.random.fold_in(key, i), shape, kind,
                           dtype_of(a, kind))
    return nest(flat)


def abstract(a: Dict) -> Dict:
    """The tree's ``jax.ShapeDtypeStruct``s."""
    return nest({p: jax.ShapeDtypeStruct(s, dtype_of(a, k))
                 for p, (s, k) in shapes(a).items()})


def _leaf(key, shape, kind, dtype):
    return _leaf_fn(tuple(shape), kind, jnp.dtype(dtype).name)(key)


_FNS: Dict = {}


def _leaf_fn(shape, kind, dtype):
    if (shape, kind, dtype) not in _FNS:
        def build(key):
            x = jax.random.normal(key, shape, jnp.float32)
            if kind == "norm":
                x = 1.0 + NORM_STD * x
            else:
                x = (BIAS_STD if kind == "bias" else STD) * x
            return x.astype(dtype)
        _FNS[(shape, kind, dtype)] = jax.jit(build)
    return _FNS[(shape, kind, dtype)]
