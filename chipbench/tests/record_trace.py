"""Record the small profiler trace that the trace-reduction tests read.

    python chipbench/tests/record_trace.py

It traces a jitted matmul, the Pallas ``chunk_accumulate`` kernel and
host annotations with a host-side gap between steps on one chip, copies
the trace to ``chiprun_out/traces/1chip.xplane.pb`` and prints a summary
of its planes, lines and event stats.  Needs a TPU.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def summarize(path: str) -> None:
    pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:6]:
                stats = {k: (str(v)[:120]) for k, v in ev.stats}
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats {stats}")


def main() -> int:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"needs a TPU, found {devs}")
    print({k: os.environ.get(k) for k in
           ("JAX_COMPILATION_CACHE_DIR", "LIBTPU_INIT_ARGS", "HOME",
            "TMPDIR", "XDG_CACHE_HOME")})
    print(devs[0].device_kind, len(devs),
          sorted(devs[0].memory_stats() or {}))
    from repro.kernels import ops
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    b = jnp.full((512, 1024), 2.0, jnp.bfloat16)
    mm = jax.jit(lambda x: (x @ x).sum())
    acc = jax.jit(ops.accumulate)
    mm(a).block_until_ready()
    acc(b, b).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            outs = [mm(a), acc(b, b)]
            jax.block_until_ready(outs)
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    out = ROOT / "chiprun_out" / "traces"
    out.mkdir(parents=True, exist_ok=True)
    dest = out / "1chip.xplane.pb"
    shutil.copy(path, dest)
    print(f"trace {os.path.getsize(dest)} bytes -> {dest}")
    summarize(str(dest))
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
