"""Serving cells: ``PagedServeEngine.tick`` under open-loop traffic on one
device.

Set-up makes the weights on the device from the seed, builds the engine
and runs one request per bucket of the engine's ladder (a prompt as long
as the bucket, one token out), so that every packed shape the traffic
can use is compiled.  The window submits, from one thread, every request
whose due time has passed before each tick; a request is timed from when
it was due, so the generator's lateness and the queue count.  After the
window the engine runs on, taking no new requests, until every request
due in the window has its first token (at most ``drain_seconds``);
requests that never get one are failed.  The gaps between tokens are
those whose later token came inside the window.
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from chipbench import bench, check, traffic as TR, weights as W


class Tracked:
    """What the harness sees of one request."""
    __slots__ = ("req", "due", "submitted", "admitted", "tokens", "done")

    def __init__(self, req, due: float, submitted: float):
        self.req = req            # the engine's request object
        self.due = due
        self.submitted = submitted
        self.admitted: Optional[float] = None   # seconds into the window
        self.tokens: List[float] = []
        self.done = 0             # the cache frontier last seen


@dataclasses.dataclass
class Window:
    tracked: Dict[int, Tracked]
    positions: List[int]          # of every real row packed in the window
    sampled: int                  # tokens sampled in the window
    end: float                    # the window's length, host clock
    rows: Tuple[int, int]         # real and padding rows in the window
    tick_s: float                 # wall time of the window's ticks
    backlog: List[Tuple[float, int]]   # (time, requests due, no token yet)

    def finished(self) -> Dict[int, Tuple[List[int], List[int]]]:
        return {rid: (list(t.req.prompt), list(t.req.out))
                for rid, t in self.tracked.items()
                if len(t.req.out) >= t.req.max_new}


def build(cell: bench.Cell, device, seed: int, faults=None):
    """The seed's weights and a warmed-up engine on ``device``."""
    from repro.core.communicator import CommConfig
    from repro.models.tp import ParallelCtx
    from repro.serving.engine import PagedServeConfig, PagedServeEngine
    with jax.default_device(device):
        params = W.make(cell.arch, seed)
        ctx = ParallelCtx(comm_config=CommConfig(**cell.traffic["comm"]))
        engine = PagedServeEngine(params, bench.program_config(cell), ctx,
                                  PagedServeConfig(**cell.traffic["engine"]))
        if faults:
            faults(engine)
        for prompt in TR.warmup_prompts(seed, engine.buckets,
                                        cell.arch["vocab"]):
            engine.submit(prompt, max_new=1)
            engine.run_until_drained()
    return engine, params


def serve_window(engine, reqs, seconds: float, *, drain_seconds: float,
                 tracer=None, on_open=None) -> Window:
    """Offer ``reqs`` open loop for ``seconds``, then drain (see module
    doc).  ``on_open()`` runs as the window opens."""
    rep0 = engine.serving_report()
    tracked: Dict[int, Tracked] = {}
    open_ids: List[int] = []
    positions: List[int] = []
    backlog: List[Tuple[float, int]] = []
    pending = 0                   # requests due without a first token
    sampled = 0
    tick_s = 0.0
    nxt = 0
    if tracer:
        tracer.start()
    t0 = time.perf_counter()
    if on_open:
        on_open(t0)

    def tick(in_window: bool) -> None:
        nonlocal sampled, pending, tick_s
        with jax.profiler.TraceAnnotation("bench.tick"):
            began = time.perf_counter()
            engine.tick()
        now = time.perf_counter() - t0
        if in_window:
            tick_s += now - (began - t0)
        with jax.profiler.TraceAnnotation("bench.observe"):
            still = []
            for rid in open_ids:
                t = tracked[rid]
                r = t.req
                if t.admitted is None and (r.row >= 0 or r.out):
                    t.admitted = now
                if in_window and r.done != t.done:
                    lo = t.done if r.done > t.done else 0   # preempted: 0
                    positions.extend(range(lo, r.done))
                t.done = r.done
                new = len(r.out) - len(t.tokens)
                if new > 0:
                    pending -= not t.tokens
                    t.tokens.extend([now] * new)
                    sampled += new if in_window else 0
                if len(r.out) < r.max_new:
                    still.append(rid)
            open_ids[:] = still
            if in_window:
                backlog.append((now, pending))

    while True:
        el = time.perf_counter() - t0
        if tracer:
            tracer.maybe_stop(el)
        if el >= seconds:
            break
        with jax.profiler.TraceAnnotation("bench.submit"):
            while nxt < len(reqs) and reqs[nxt].due <= el:
                q = reqs[nxt]
                rid = engine.submit(q.prompt, max_new=q.max_new)
                tracked[rid] = Tracked(engine.sched.queue[-1], q.due, el)
                open_ids.append(rid)
                pending += 1
                nxt += 1
        if engine.sched.has_work():
            tick(True)
        else:
            with jax.profiler.TraceAnnotation("bench.idle"):
                due = reqs[nxt].due if nxt < len(reqs) else seconds
                time.sleep(max(0.0, min(due, seconds) - el))
    end = time.perf_counter() - t0
    rep1 = engine.serving_report()
    while (any(not t.tokens for t in tracked.values())
           and time.perf_counter() - t0 < end + drain_seconds
           and engine.sched.has_work()):
        tick(False)
    rows = (rep1["rows"]["real"] - rep0["rows"]["real"],
            rep1["rows"]["padded"] - rep0["rows"]["padded"])
    return Window(tracked, positions, sampled, end, rows, tick_s, backlog)


def latencies(win: Window) -> Dict[str, List[float]]:
    ts = win.tracked.values()
    return {
        "ttft": [t.tokens[0] - t.due for t in ts if t.tokens],
        "itl": [b - a for t in ts for a, b in zip(t.tokens, t.tokens[1:])
                if b <= win.end],
        "admit_wait": [t.admitted - t.due for t in ts
                       if t.admitted is not None],
        "late": [t.submitted - t.due for t in ts],
    }


def schedule(cell: bench.Cell, seed: int, seconds: float, **arrivals):
    return TR.open_loop(seed, seconds=seconds, vocab=cell.arch["vocab"],
                        **{**cell.traffic["arrivals"], **arrivals})


def run(cell: bench.Cell, devices, *, seed: int, seconds: float,
        trace: bool, t_start: float, faults=None) -> Dict:
    """One run of a serving cell.  ``faults`` (tests only) may break the
    engine after it is built: ``faults(engine)``."""
    tr = cell.traffic
    run = bench.Run(cell, seed, seconds, bench.peaks(devices[0].device_kind),
                    len(devices))
    engine, params = build(cell, devices[0], seed, faults)
    reqs = schedule(cell, seed, seconds)
    tracer = bench.Tracer(tr["trace_seconds"]) if trace else None

    def opened(t0):
        run.setup_s = t0 - t_start

    with jax.default_device(devices[0]):
        win = serve_window(engine, reqs, seconds, tracer=tracer,
                           drain_seconds=tr["drain_seconds"], on_open=opened)
    run.window_s = win.end
    mem = bench.memory_peak(devices)
    engine.close()
    del engine

    lat = latencies(win)
    print(f"generator lateness: median {np.median(lat['late'])} s, max "
          f"{max(lat['late'])} s over {len(lat['late'])} requests",
          file=sys.stderr)
    run.data.update(ttft=lat["ttft"], itl=lat["itl"],
                    admit_wait=lat["admit_wait"], positions=win.positions,
                    sampled=win.sampled, tick_s=win.tick_s,
                    rows_real=win.rows[0],
                    rows_padded=win.rows[1])
    if tracer:
        run.trace = tracer.reduce()
        if run.trace is not None:
            from chipbench import trace as T
            run.trace_window = T.window(run.trace, "bench.window")
    failed = len(win.tracked) - len(lat["ttft"])

    finished = win.finished()
    pick = check.sample_requests(finished, tr["check"]["requests"], seed)
    gap = check.served_gaps(cell.arch, params, [finished[r] for r in pick])
    print(f"compared {sum(len(finished[r][1]) for r in pick)} served tokens "
          f"of {len(pick)} requests", file=sys.stderr)
    ok, compared = check.compared({"served_logit_gap": gap}, tr["limits"])
    return bench.result_line(run, trace=trace,
                             correct=ok and bool(pick) and failed == 0,
                             attempted=len(win.tracked), failed=failed,
                             compared=compared, devices=devices,
                             memory_peak_bytes=mem)
