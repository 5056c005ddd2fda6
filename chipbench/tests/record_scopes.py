"""Record the profiler trace that ``test_recorded.py`` reads: a few ticks
of the serving engine at the CPU tests' size on one chip, with the
program's ``serve.*`` phase spans and the harness's ``bench.tick``
around each, and the compiled HLO text of the step at every bucket.

    python chipbench/tests/record_scopes.py

Writes ``chiprun_out/traces/scoped_ticks.xplane.pb`` and
``scoped_ticks.<bucket>.hlo.txt.gz``.  Needs a TPU.
"""

from __future__ import annotations

import glob
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import bench, scopes as S  # noqa: E402
from chipbench.drivers import serve as D  # noqa: E402
from chipbench.tests import tiny  # noqa: E402


def main() -> int:
    devices = bench.require_devices(1)
    bench.enable_compile_cache()
    cell = tiny.tiny_serve()
    engine, _ = D.build(cell, devices[0], seed=7)
    for n in (30, 3, 20, 1):                  # a prefill-heavy tick first
        engine.submit(list(range(1, n + 1)), max_new=4)
    tmp = tempfile.mkdtemp()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0           # annotations only: small
    # the device warm-up compiled for: another default retraces
    with jax.default_device(devices[0]):
        engine.tick()
        jax.profiler.start_trace(tmp, profiler_options=options)
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.tick"):
                engine.tick()
        jax.profiler.stop_trace()
    engine.close()
    out = ROOT / "chiprun_out" / "traces"
    out.mkdir(parents=True, exist_ok=True)
    path = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(path, out / "scoped_ticks.xplane.pb")
    shutil.rmtree(tmp)
    for b, text in zip(engine.buckets, S.step_texts(cell)):
        with gzip.open(out / f"scoped_ticks.{b}.hlo.txt.gz", "wt") as f:
            f.write(text)
    print(f"trace and {len(engine.buckets)} HLO texts -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
