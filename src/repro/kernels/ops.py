"""Jitted public wrappers around the Pallas kernels.

These handle the padding/alignment contracts (arbitrary shapes -> lane- and
block-aligned payloads) and pick the kernel mode from the default backend:
compiled on TPU, interpret=True on CPU so CPU tests execute the same kernel
body.  Any other backend is an error, never a quiet interpreted run.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import chunk_accumulate as _ca
from repro.kernels import codec as _codec
from repro.kernels import flash_decode as _fd
from repro.kernels import payload_partition as _pp


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels compile for TPU and interpret on CPU; the "
            f"default backend is {backend!r}")
    return backend == "cpu"


def _pad_2d(x: jax.Array) -> jax.Array:
    """Flatten + zero-pad to the [rows, LANE] tile shape the kernels need."""
    n = x.size
    cols = _ca.LANE
    rows = -(-n // cols)
    rows_pad = (-rows) % _ca.SUBLANE
    pad = rows * cols - n + rows_pad * cols
    return jnp.pad(x.reshape(-1), (0, pad)).reshape(rows + rows_pad, cols)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _accumulate(a: jax.Array, b: jax.Array, acc_dtype):
    n = a.size
    af, bf = _pad_2d(a), _pad_2d(b)
    out = _ca.chunk_accumulate_2d(af, bf, acc_dtype=acc_dtype,
                                  interpret=_interpret())
    return out.reshape(-1)[:n].reshape(a.shape)


def _accumulate_fwd(a, b, acc_dtype):
    return _accumulate(a, b, acc_dtype), None


def _accumulate_bwd(acc_dtype, _res, g):
    # d(a + b)/da = d(a + b)/db = identity: the cotangent passes through
    # to both operands exactly.  Without this VJP the raw pallas_call is
    # opaque to AD, and any differentiated collective on the staged ring
    # (every bf16-param train step under ACC_AUTO) fails to lower.
    return g, g


_accumulate.defvjp(_accumulate_fwd, _accumulate_bwd)


@functools.partial(jax.jit, static_argnames=("acc_dtype",))
def accumulate(a: jax.Array, b: jax.Array, *, acc_dtype=jnp.float32):
    """Ring-step accumulate for arbitrary-shaped chunks (pads to tiles)."""
    assert a.shape == b.shape and a.dtype == b.dtype
    return _accumulate(a, b, acc_dtype)


def ring_accumulate_fn(acc_dtype=jnp.float32):
    """An ``accumulate(a, b)`` closure for collectives.ring_reduce_scatter /
    ring_all_reduce — this is how the kernel plugs into the staged path."""
    return lambda a, b: accumulate(a, b, acc_dtype=acc_dtype)


@functools.partial(jax.jit, static_argnames=("start_block", "n_blocks",
                                             "block"))
def extract_segment(x: jax.Array, start_block: int, n_blocks: int,
                    block: int = _pp.BLOCK) -> jax.Array:
    """Aligned segment copy (payload split)."""
    return _pp.extract_segment(x, start_block, n_blocks, block=block,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block",))
def merge_segments(segments: Sequence[jax.Array],
                   block: int = _pp.BLOCK) -> jax.Array:
    """Per-route result reassembly (payload merge)."""
    return _pp.merge_segments(list(segments), block=block,
                              interpret=_interpret())


# --- wire codecs (DESIGN.md §12) -------------------------------------------
#
# The flat-payload face of kernels/codec.py: arbitrary-shaped chunks are
# padded to [rows, LANE] tiles, encoded to their wire form (fp8 values +
# per-row f32 scales, or a bf16 half-width pack), and decoded — plain or
# fused into the ring-step accumulate.  AD never reaches these pallas_calls:
# the differentiated entry points are the straight-through composites in
# core/collectives.py, and the error-feedback roundtrip runs on already-
# computed gradients.

@functools.partial(jax.jit, static_argnames=("codec_name",))
def wire_encode(x: jax.Array, *, codec_name: str):
    """Encode a chunk for the wire -> (values_2d, scales_or_None)."""
    x2 = _pad_2d(x)
    if codec_name == "bf16_pack":
        return _codec.bf16_pack_2d(x2, interpret=_interpret()), None
    vals, scales = _codec.fp8_encode_2d(x2, fmt=codec_name,
                                        interpret=_interpret())
    return vals, scales


@functools.partial(jax.jit, static_argnames=("codec_name", "shape", "dtype"))
def wire_decode(vals: jax.Array, scales, *, codec_name: str,
                shape, dtype) -> jax.Array:
    """Decode a wire payload back to ``shape``/``dtype``."""
    n = 1
    for d in shape:
        n *= d
    if codec_name == "bf16_pack":
        out2 = vals.astype(dtype)
    else:
        out2 = _codec.fp8_decode_2d(vals, scales, out_dtype=dtype,
                                    interpret=_interpret())
    return out2.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("codec_name", "acc_dtype"))
def wire_decode_accumulate(vals: jax.Array, scales, mine: jax.Array, *,
                           codec_name: str, acc_dtype=jnp.float32):
    """Fused ring-step decompress: out = dequant(vals[, scales]) + mine.

    The bf16 pack feeds the existing fp32 chunk_accumulate directly (its
    decode IS the accumulate's upcast); fp8 runs the fused
    dequantize-accumulate kernel.  Accumulation is fp32 either way — the
    staged-reduce contract of resolve_accumulate.
    """
    m2 = _pad_2d(mine)
    if codec_name == "bf16_pack":
        out2 = _ca.chunk_accumulate_2d(m2, vals, acc_dtype=acc_dtype,
                                       interpret=_interpret())
    else:
        out2 = _codec.fp8_decode_accumulate_2d(vals, scales, m2,
                                               acc_dtype=acc_dtype,
                                               interpret=_interpret())
    return out2.reshape(-1)[:mine.size].reshape(mine.shape)


# --- paged flash-decoding attention (DESIGN.md §13) -------------------------

@functools.partial(jax.jit, static_argnames=("window",))
def paged_flash_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       block_tables: jax.Array, kv_valid: jax.Array, *,
                       window=None) -> jax.Array:
    """Flash-decoding over a paged KV pool (one layer): q [T, Hq, hd],
    pools [n_blocks, block_size, Hkv, hd], block_tables [T, maxb],
    kv_valid [T] -> [T, Hq, hd].  Compiled on TPU, interpreted on CPU."""
    return _fd.paged_flash_decode_pool(q, k_pool, v_pool, block_tables,
                                       kv_valid, window=window,
                                       interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("codec_name",))
def wire_roundtrip(x: jax.Array, *, codec_name: str) -> jax.Array:
    """encode -> decode, same shape/dtype: the local quantization a chunk
    suffers on the wire.  Error feedback (train/bucketer.py) subtracts this
    from the pre-send gradient to build the next step's residual."""
    vals, scales = wire_encode(x, codec_name=codec_name)
    return wire_decode(vals, scales, codec_name=codec_name,
                       shape=x.shape, dtype=x.dtype)
