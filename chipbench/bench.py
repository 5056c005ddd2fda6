"""What every cell's run shares: finding the cell's files by name, the
device check, the compile cache, tracing, the per-layer metric readers,
and the result line.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``; it names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``).  The traffic file's ``driver`` names the
module under ``drivers/`` that runs it.  Every metric, end to end or per
layer, is read by ``metrics/<name>.py``, whose ``read(run)`` returns a
number, or None where the run gave it nothing to read.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent


def process_start() -> float:
    """``time.perf_counter()`` at the moment this process was created."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        age = (time.clock_gettime(time.CLOCK_BOOTTIME)
               - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
        return time.perf_counter() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter()


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # configs/<config>.json
    traffic: Dict[str, Any]      # traffic/<traffic>.json
    end_to_end: List[Dict]       # BENCHMARK.json metrics this cell reports
    per_layer: List[Dict]

    @property
    def arch(self) -> Dict[str, Any]:
        return self.config["arch"]


def program_config(cell: Cell):
    """The program's ArchConfig for the cell: its named configuration
    with every size the configuration file gives."""
    from repro.configs import get_config
    from repro.models.config import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    base = get_config(cell.config["model"])
    cfg = dataclasses.replace(
        base, **{k: v for k, v in cell.arch.items() if k in fields})
    cfg.validate()
    return cfg


def _reports(metric: Dict, cell: str, e2e_names=None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    lists, else every cell (end to end) or every cell that reports the
    end-to-end metric it moves (per layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str, root: Path) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = json.loads((HERE / "configs" / f"{entry['config']}.json")
                        .read_text())
    traffic = json.loads((HERE / "traffic" / f"{entry['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, entry["chips"], config, traffic, e2e, per_layer)


def require_devices(chips: int) -> list:
    """The cell's TPU devices; exits non-zero on any other platform or
    with fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's devices are {devices[0].platform} "
              f"({len(devices)})", file=sys.stderr)
        raise SystemExit(3)
    if len(devices) < chips:
        print(f"the cell needs {chips} TPU chips, found {len(devices)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/`` in the checkout),
    for every program however fast it compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json")
    return table[device_kind]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class Tracer:
    """The profiler over the first ``seconds`` of the window, in a
    temporary directory that ``reduce`` reads and removes."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir: Optional[str] = None
        self._ann = None
        self.active = False

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.active = True

    def maybe_stop(self, elapsed: float) -> None:
        if self.active and elapsed >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        if not self.active:
            return
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self):
        from chipbench import trace as T
        self.stop()
        try:
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if not path:
                return None
            return T.load(path[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Run:
    """What a driver hands the metric readers."""
    cell: Cell
    seed: int
    seconds: float
    peak: Dict[str, float]
    chips: int
    setup_s: float = math.nan
    window_s: float = math.nan           # measured window, host clock
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None                    # chipbench.trace.Trace or None
    trace_window: Any = None             # (lo, hi) seconds on its clock


def read_metric(name: str, run: Run) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    if value is None:
        return None
    value = float(value)
    return value if math.isfinite(value) else None


def driver(cell: Cell):
    return importlib.import_module(f"chipbench.drivers.{cell.traffic['driver']}")


def result_line(run: Run, *, trace: bool, correct: bool, attempted: int,
                failed: int, compared: Dict[str, Dict[str, float]],
                devices, memory_peak_bytes: int) -> Dict[str, Any]:
    metrics = {}
    for m in (run.cell.per_layer if trace else run.cell.end_to_end):
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak_bytes}
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if trace and run.trace is not None:
        from chipbench import trace as T
        lo, hi = run.trace_window
        device["busy_s"] = sum(T.total(T.busy(dv, lo, hi)) for dv in
                               run.trace.devices.values()) / \
            max(len(run.trace.devices), 1)
        device["window_s"] = hi - lo
        out["breakdown"] = {"device_ops": T.op_breakdown(run.trace, lo, hi),
                            "idle_gaps": T.gap_breakdown(run.trace, lo, hi)}
    out["compared"] = compared
    return out


def print_result(out: Dict[str, Any]) -> None:
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
