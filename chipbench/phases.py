"""Where a serving cell's time goes, read from the program's own spans,
scopes and stamps: one run of the cell as ``run.py`` makes it, reduced to

- host ms per tick in each ``serve.*`` phase of ``PagedServeEngine.tick``
  (median over the traced ticks), and the device's idle seconds named by
  the phase the host was in;
- ``fetch_after_step_ms``: from the end of each step on the device (its
  ``jit_paged_step`` span on the ``XLA Modules`` line) to the end of the
  tick's ``serve.fetch``, the logits' copy and the host's wake-up.  It
  holds the offset between the device's clock and the host's, which a
  trace cannot tell apart (about a millisecond on a v5e);
- ``host_ms``: per tick, the phases other than ``serve.await`` and
  ``serve.fetch``, plus ``fetch_after_step_ms`` (median);
- device own seconds by named scope (``scopes.py``);
- over the requests due in the window that got a first token, the 80th
  percentile of first admission to first token and the share of the ticks
  between in which the request was admitted but got no row (the
  scheduler's stamps);
- every tick of the window longer than ``LONG_TICK_S``.

    python chipbench/phases.py --workload <cell> --seed <n> \\
        --seconds <s> [--trace 0|1] [--out <file>]

The trace records the program's spans without the profiler's Python call
tracer, which the benchmark's traced runs keep and which slows each tick.
Correctness is not checked.  Prints one JSON object; needs a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from chipbench import bench, scopes as S, trace as T  # noqa: E402

PHASES = ("serve.plan", "serve.pack", "serve.issue", "serve.await",
          "serve.fetch", "serve.sample", "serve.commit")
STEP = "jit_paged_step"
LONG_TICK_S = 0.4


class Tracer(bench.Tracer):
    """The harness's tracer without the Python call tracer, read with the
    program's ``serve.*`` spans and the device's step spans."""

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-phases-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.active = True

    def reduce(self):
        self.stop()
        try:
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if not path:
                return None, []
            return (T.load(path[0], host_prefixes=("bench.", "serve.")),
                    step_spans(path[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def step_spans(path: str, device: int = 0) -> List[T.Interval]:
    """[start, end) of each execution of the serving step on ``device``,
    in time order."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name != f"/device:TPU:{device}":
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out += [(ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events if ev.name.startswith(STEP)]
    return sorted(out)


def tick_phases(trace: T.Trace, lo: float, hi: float
                ) -> List[Dict[str, T.Span]]:
    """The ``serve.*`` spans of each tick that starts in [lo, hi) and
    issues a step, by phase."""
    out = []
    for tick in trace.host:
        if tick.name != "bench.tick" or not lo <= tick.start < hi:
            continue
        spans = {s.name: s for s in trace.host if s.name in PHASES
                 and tick.start <= s.start < tick.end}
        if "serve.issue" in spans:
            out.append(spans)
    return out


def host_phases(ticks: List[Dict[str, T.Span]], steps: List[T.Interval]
                ) -> Dict:
    """Median ms per tick of each phase; with the step spans (one a tick,
    in order), ``fetch_after_step_ms`` and ``host_ms`` (module doc)."""
    ms = lambda x: 1e3 * x  # noqa: E731
    out: Dict = {"ticks": len(ticks), "phases_ms": {
        p: ms(statistics.median(t[p].end - t[p].start for t in ticks))
        for p in PHASES if ticks and all(p in t for t in ticks)}}
    if not ticks or len(steps) != len(ticks):
        return out
    after = [t["serve.fetch"].end - s[1] for t, s in zip(ticks, steps)]
    host = [a + sum(t[p].end - t[p].start for p in PHASES
                    if p not in ("serve.await", "serve.fetch"))
            for t, a in zip(ticks, after)]
    out["fetch_after_step_ms"] = {"median": ms(statistics.median(after)),
                                  "max": ms(max(after)),
                                  "min": ms(min(after))}
    out["host_ms"] = ms(statistics.median(host))
    return out


def prefill(reqs) -> Dict:
    """First admission to first token over the requests that have one:
    its 80th percentile in ms, and the share of stalled ticks."""
    got = [r for r in reqs if r.t_first is not None]
    ticks = sum(r.stall_ticks + r.prefill_ticks for r in got)
    return {"requests": len(got),
            "p80_ms": float(np.percentile([r.t_first - r.t_admit
                                           for r in got], 80)) * 1e3
            if got else None,
            "stall_share": sum(r.stall_ticks for r in got) / ticks
            if ticks else None}


def main(argv=None) -> int:
    from chipbench.drivers import serve as D
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default="", help="also write the JSON here")
    args = ap.parse_args(argv)
    import jax
    cell = bench.load_cell(args.workload, ROOT)
    devices = bench.require_devices(cell.chips)
    bench.enable_compile_cache()
    engine, params = D.build(cell, devices[0], args.seed)
    tracer = Tracer(cell.traffic["trace_seconds"]) if args.trace else None
    timed: List[Tuple[float, float]] = []
    tick = engine.tick

    def timed_tick():
        began = time.perf_counter()
        n = tick()
        timed.append((began, time.perf_counter() - began))
        return n

    engine.tick = timed_tick
    opened: List[float] = []
    with jax.default_device(devices[0]):
        win = D.serve_window(engine, D.schedule(cell, args.seed, args.seconds),
                             args.seconds, tracer=tracer,
                             drain_seconds=cell.traffic["drain_seconds"],
                             on_open=opened.append)
    engine.close()
    del engine, params
    lat = D.latencies(win)
    out: Dict = {
        "workload": cell.name, "seed": args.seed, "trace": bool(args.trace),
        "ttft_p80_ms": float(np.percentile(lat["ttft"], 80)) * 1e3,
        "itl_p95_ms": float(np.percentile(lat["itl"], 95)) * 1e3,
        "prefill": prefill(t.req for t in win.tracked.values()),
        "long_ticks": [[b - opened[0], s] for b, s in timed
                       if s > LONG_TICK_S and b - opened[0] < win.end],
    }
    if tracer:
        trace, steps = tracer.reduce()
        if trace is not None:
            lo, hi = T.window(trace, "bench.window")
            out.update(host_phases(tick_phases(trace, lo, hi), steps))
            out["window_s"] = hi - lo
            out["busy_s"] = [T.total(T.busy(dev, lo, hi))
                             for dev in trace.devices.values()]
            out["idle_gaps"] = T.gap_breakdown(trace, lo, hi)
            texts = S.step_texts(cell)
            out["scopes"] = S.breakdown(trace, S.assign(trace, texts or []),
                                        lo, hi)
    text = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
