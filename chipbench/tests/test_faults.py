"""Whole runs of the serving cell at a size a CPU holds, past the
harness's look for a chip: a sound run is correct, and a token altered
where it is produced makes ``correct`` come out false under the limit the
traffic file gives.  So does the cell's control, the reference in float8
put in the program's place, read as ``chipbench/calibrate.py`` reads it
on the chip."""

import time

import jax
import pytest

from chipbench import bench, calibrate, check
from chipbench.tests import tiny


def _run(cell, faults=None, seconds=1.0, seed=2 ** 33 + 3):
    return bench.driver(cell).run(cell, jax.devices()[:1], seed=seed,
                                  seconds=seconds, trace=False,
                                  t_start=time.perf_counter(), faults=faults)


@pytest.fixture(scope="module")
def serve_cell():
    return tiny.tiny_serve()


def test_serve_sound_run_is_correct(cpu_peaks, serve_cell):
    out = _run(serve_cell, seconds=3.0)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 20 and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_ttft_p80_ms", "serve_itl_p95_ms",
                                   "setup_s"}
    assert list(out)[-1] == "compared"


def test_serve_altered_token_is_caught(cpu_peaks, serve_cell):
    def alter(engine):
        sample = engine._sample
        vocab = serve_cell.arch["vocab"]

        def wrong(logits, temperature):
            return (sample(logits, temperature) + 1) % vocab
        engine._sample = wrong
    out = _run(serve_cell, faults=alter, seconds=3.0)
    assert not out["correct"], out["compared"]


def test_serve_control_is_caught(serve_cell):
    limits = serve_cell.traffic["limits"]
    for row in calibrate.serve(serve_cell, jax.devices()[:1], [5, 2 ** 34],
                               {5, 2 ** 34}, 3.0):
        assert check.compared(row["program"], limits)[0], row
        assert not check.compared(row["control"], limits)[0], row


def test_no_tpu_exits_non_zero(tmp_path):
    import subprocess
    import sys
    from chipbench.tests.tiny import ROOT
    r = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "serve.deepseek-67b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)}, cwd=ROOT, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
