"""End-to-end smoke run of the system on TPU chips.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host with four chips

One chip: every Pallas kernel of the main path runs compiled and is
compared with its oracle in ``repro.kernels.ref``; then ``repro.launch.serve``
serves 8 requests with glm4-9b at its published widths, cut to 16 layers.

Four chips, on a ("data", "model") mesh of (2, 2): the FlexLink
all-reduce, all-gather and reduce-scatter over ``data`` against the native
XLA collectives, then ``repro.launch.train`` for three steps at glm4-9b
widths (2 layers) with the flexlink backend and with the native one.

Everything runs in this one process: a chip belongs to the process that
first touches it.  The script fails, and prints no result, unless JAX's
devices are TPUs.  Its last line of output is one JSON object naming the
device.  The serve and train figures it prints are smoke figures, not
benchmarks.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
OUT = ROOT / "chiprun_out"
MiB = 2 ** 20

#: glm4-9b attention heads: query heads, KV heads, head dim; KV block size
GLM4_HEADS = (32, 2, 128)
KV_BLOCK = 16

TRAIN_ARGV = ["--arch", "glm4-9b", "--layers", "2", "--seq-len", "512",
              "--batch", "8", "--mesh-shape", "2,2", "--steps", "3"]
#: First-step losses of the flexlink and native backends must agree to one
#: bf16 rounding step of the loss (2^-8 relative).  Both backends sum the
#: same values: the staged ring accumulates in f32 and rounds each reduced
#: element to bf16 once, as the native all-reduce does, so only the order
#: of rounding differs.  That moves single activations by at most an ulp,
#: and the loss, an f32 mean over 4,096 token NLLs, by far less.
TRAIN_LOSS_RTOL = 2.0 ** -8


def require_tpu(count: int) -> list:
    """The TPU devices, or exit non-zero: nothing here falls back."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU found: JAX's devices are "
                         f"{devices[0].platform} ({len(devices)})")
    if len(devices) < count:
        raise SystemExit(f"needs {count} TPU chips, found {len(devices)}")
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", flush=True)
    return devices


def _run_compiled(name: str, fn, *args, **static):
    """Compile ``fn`` for the arguments, check that a Pallas kernel is in
    the compiled program, and run it."""
    compiled = fn.lower(*args, **static).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{name}: no Pallas TPU kernel in the "
                             f"compiled program")
    return compiled(*args)


def _f64(x) -> np.ndarray:
    return np.asarray(x, np.float64)


def kernel_phase(*, payload_bytes: int = 16 * MiB,
                 codec_bytes: int = 4 * MiB, heads=GLM4_HEADS,
                 pool_blocks: int = 512, max_blocks: int = 32,
                 rows: int = 32) -> None:
    """Each kernel on the device against its oracle, at the tolerance of
    the kernel's own tests."""
    from repro.kernels import ops, ref
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    # staged-ring reduce step: bf16 payload, f32 accumulate
    n = payload_bytes // 2
    a = jax.random.normal(next(keys), (n,), jnp.bfloat16)
    b = jax.random.normal(next(keys), (n,), jnp.bfloat16)
    got = _run_compiled("chunk_accumulate", ops.accumulate, a, b)
    np.testing.assert_allclose(_f64(got),
                               _f64(ref.chunk_accumulate_ref(a, b)))
    print(f"kernel chunk_accumulate: bf16 {payload_bytes // MiB} MiB "
          f"matches the oracle", flush=True)

    # wire codecs on the canonical [rows, 128] f32 layout
    x = 3.0 * jax.random.normal(next(keys), (codec_bytes // 4 // 128, 128))
    mine = 3.0 * jax.random.normal(next(keys), x.shape)
    vals, scales = _run_compiled("wire_encode[fp8_e4m3]", ops.wire_encode,
                                 x, codec_name="fp8_e4m3")
    wvals, wscales = ref.fp8_encode_ref(x, fmt="fp8_e4m3")
    np.testing.assert_array_equal(np.asarray(vals.astype(jnp.float32)),
                                  np.asarray(wvals.astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(wscales),
                               rtol=1e-6)
    got = _run_compiled("wire_decode_accumulate[fp8_e4m3]",
                        ops.wire_decode_accumulate, vals, scales, mine,
                        codec_name="fp8_e4m3")
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.fp8_decode_accumulate_ref(wvals, wscales, mine)),
        rtol=1e-5, atol=1e-5)
    vals, scales = _run_compiled("wire_encode[bf16_pack]", ops.wire_encode,
                                 x, codec_name="bf16_pack")
    if scales is not None:
        raise AssertionError("bf16_pack must ship no scales")
    np.testing.assert_array_equal(np.asarray(vals),
                                  np.asarray(ref.bf16_pack_ref(x)))
    got = _run_compiled("wire_decode_accumulate[bf16_pack]",
                        ops.wire_decode_accumulate, vals, None, mine,
                        codec_name="bf16_pack")
    np.testing.assert_allclose(_f64(got),
                               _f64(ref.chunk_accumulate_ref(mine, vals)))
    print(f"kernel wire codecs: fp8_e4m3 and bf16_pack, f32 "
          f"{codec_bytes // MiB} MiB, match the oracles", flush=True)

    # paged flash decoding over a bf16 pool, ragged kv_valid, pad rows
    hq, hkv, hd = heads
    maxb = max_blocks
    q = jax.random.normal(next(keys), (rows, hq, hd), jnp.bfloat16)
    kp = jax.random.normal(next(keys), (pool_blocks, KV_BLOCK, hkv, hd),
                           jnp.bfloat16)
    vp = jax.random.normal(next(keys), kp.shape, jnp.bfloat16)
    tables = jax.random.randint(next(keys), (rows, maxb), 0, pool_blocks,
                                jnp.int32)
    kv_valid = jax.random.randint(next(keys), (rows,), 1,
                                  maxb * KV_BLOCK + 1, jnp.int32)
    kv_valid = kv_valid.at[-2:].set(0)
    got = _run_compiled("paged_flash_decode", ops.paged_flash_decode,
                        q, kp, vp, tables, kv_valid)
    want = ref.paged_flash_decode_ref(q, kp, vp, tables, kv_valid)
    np.testing.assert_allclose(_f64(got), _f64(want), atol=2e-2)
    np.testing.assert_array_equal(np.asarray(got[-2:]), 0)   # pad rows
    print(f"kernel paged_flash_decode: Hq {hq} Hkv {hkv} hd {hd}, "
          f"{pool_blocks} blocks of {KV_BLOCK}, {rows} rows match the "
          f"oracle", flush=True)


def serve_phase(*, size=("--layers", "16"), requests: int = 8,
                max_new: int = 16) -> None:
    """``repro.launch.serve`` in this process, glm4-9b on the paged
    engine with mixed short and long requests; every request must finish
    with the number of tokens it asked for."""
    from repro.configs import get_config
    from repro.launch import serve
    record = OUT / "smoke_serve.json"
    t0 = time.perf_counter()
    rc = serve.main(["--arch", "glm4-9b", *size, "--paged", "on",
                     "--requests", str(requests), "--max-new", str(max_new),
                     "--mixed", "--out", str(record)])
    wall = time.perf_counter() - t0
    if rc != 0:
        raise AssertionError(f"serve exited {rc}")
    rec = json.loads(record.read_text())
    work = serve.build_workload(np.random.default_rng(0), requests,
                                get_config("glm4-9b").vocab, max_new, True)
    want_tokens = sum(m for _, m in work)
    if rec["requests"] != requests or rec["tokens"] != want_tokens:
        raise AssertionError(f"served {rec['requests']}/{requests} "
                             f"requests, {rec['tokens']}/{want_tokens} "
                             f"tokens")
    print(f"serve smoke figure, not a benchmark: {rec['requests']} "
          f"requests, {rec['tokens']} tokens, {rec['wall_s']} s draining, "
          f"{wall} s with set-up and compiles", flush=True)


def collectives_phase(mesh, *, sizes=(1 * MiB, 16 * MiB),
                      dtypes=(jnp.bfloat16, jnp.float32)) -> None:
    """FlexLink collectives over ``data`` against the native ones."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P
    from repro.core.communicator import CommConfig, FlexCommunicator
    from repro.core.topology import Collective
    n_data = mesh.shape["data"]
    comm = FlexCommunicator("data", n_data, CommConfig(profile="tpu_v5e"),
                            ortho_name="model")
    key = jax.random.PRNGKey(1)

    def run(fn, x, out_spec):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                                 out_specs=out_spec, check_vma=False))(x)

    cases = (
        (Collective.ALL_REDUCE, comm.all_reduce,
         lambda v: lax.psum(v, "data"), P("data")),
        (Collective.ALL_GATHER, comm.all_gather,
         lambda v: lax.all_gather(v, "data", tiled=True), P()),
        (Collective.REDUCE_SCATTER, comm.reduce_scatter,
         lambda v: lax.psum_scatter(v, "data", scatter_dimension=0,
                                    tiled=True), P("data")),
    )
    staged = {}
    for op, flex, native, out_spec in cases:
        for size in sizes:
            for dtype in dtypes:
                n = size // jnp.dtype(dtype).itemsize
                # a rank's payload; reduce-scatter's rows are its chunks
                shape = ((n_data, n // n_data)
                         if op == Collective.REDUCE_SCATTER else (n,))
                gshape = (n_data * shape[0],) + shape[1:]
                key, sub = jax.random.split(key)
                if op == Collective.ALL_GATHER:
                    x = jax.random.normal(sub, gshape, dtype)
                else:
                    # small integers: every summation order is exact
                    x = jax.random.randint(sub, gshape, -8, 8).astype(dtype)
                plan = comm.plan_for(op, jax.ShapeDtypeStruct(shape, dtype))
                units = plan.units()
                staged[(op, size)] = units.get("staged", 0)
                got = run(flex, x, out_spec)
                want = run(native, x, out_spec)
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
                print(f"collective {op.value} {size // MiB} MiB "
                      f"{jnp.dtype(dtype).name}: plan units {units} of "
                      f"{plan.grain}, bit-exact vs native", flush=True)
    big = max(sizes)
    for op, *_ in cases:
        if staged[(op, big)] == 0:
            raise AssertionError(f"{op.value} at {big // MiB} MiB sent "
                                 f"nothing over the staged ring")


def train_phase(argv=TRAIN_ARGV, rtol: float = TRAIN_LOSS_RTOL) -> None:
    """``repro.launch.train`` with both backends; finite losses, and the
    first-step losses agree within ``rtol``."""
    from repro.core.communicator import comm_destroy_all
    from repro.launch import train
    losses = {}
    for backend in ("flexlink", "nccl"):
        comm_destroy_all()
        record = OUT / f"smoke_train_{backend}.json"
        rc = train.main([*argv, "--backend", backend, "--out", str(record)])
        if rc != 0:
            raise AssertionError(f"train --backend {backend} exited {rc}")
        losses[backend] = json.loads(record.read_text())["losses"]
        if not all(math.isfinite(v) for v in losses[backend]):
            raise AssertionError(f"{backend} losses {losses[backend]}")
        print(f"train {backend}: losses {losses[backend]}", flush=True)
    first = {b: v[0] for b, v in losses.items()}
    if abs(first["flexlink"] - first["nccl"]) > rtol * abs(first["nccl"]):
        raise AssertionError(f"first-step losses differ beyond rtol {rtol}: "
                             f"{first}")
    print(f"train: first-step losses {first} agree within rtol {rtol}",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    OUT.mkdir(exist_ok=True)
    if args.chips == 4:
        mesh = jax.sharding.Mesh(np.asarray(devices[:4]).reshape(2, 2),
                                 ("data", "model"))
        collectives_phase(mesh)
        train_phase()
    else:
        kernel_phase()
        serve_phase()
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
