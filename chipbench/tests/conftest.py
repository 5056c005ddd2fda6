"""CPU set-up for the benchmark's own tests: four virtual devices, the
repository's sources on the path, and test peaks in place of the chip's
(a CPU has no entry in peaks.json)."""

import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

TEST_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture
def cpu_peaks(monkeypatch):
    from chipbench import bench
    monkeypatch.setattr(bench, "peaks", lambda kind: TEST_PEAKS)
    return TEST_PEAKS
