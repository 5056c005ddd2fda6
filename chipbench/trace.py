"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
and the breakdown read: device busy time, device time by operation class
(collective kinds and Pallas kernels by name among them), and idle gaps
named by the harness annotation the host was inside.

Only ``jax.profiler.ProfileData`` is used.  On a TPU the trace holds one
plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line lists every
operation the TensorCore ran, one at a time, named by its HLO instruction
text (``%name = type opcode(operands), ...``); the other lines (``Async
XLA Ops``, ``XLA Modules``) are not read.  The host plane
``/host:CPU`` holds the ``jax.profiler.TraceAnnotation`` spans.  Event
times are nanoseconds on one clock; a device's clock may sit a
millisecond or two off the host's, so a gap is named by the annotation
that covers its midpoint.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation: HLO instruction name, opcode, [start, end)
    in seconds, and its full instruction text."""
    name: str
    opcode: str
    start: float
    end: float
    text: str

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def collective(self) -> Optional[str]:
        """The collective kind, or None."""
        for kind in COLLECTIVES:
            if self.opcode.startswith(kind) or self.name.startswith(kind):
                return kind
        return None

    @property
    def kernel(self) -> Optional[str]:
        """A Pallas kernel's name (the instruction name without its
        numeric suffix), or None for any other operation."""
        if self.opcode != "custom-call" or "tpu_custom_call" not in self.text:
            return None
        return re.sub(r"\.\d+$", "", self.name)

    @property
    def label(self) -> str:
        """The class the breakdown sums this operation under."""
        if self.collective:
            return self.collective
        if self.kernel:
            return f"pallas:{self.kernel}"
        if self.opcode == "fusion":
            base = re.sub(r"(\.\d+)+$", "", self.name)
            return f"fusion:{base}"
        return self.opcode


@dataclasses.dataclass
class Device:
    ops: List[Op]            # the TensorCore's operations, in time order
    #: each op's own time: its duration less that of the ops nested in
    #: it (a ``while`` loop's span holds its body's operations)
    self_s: List[float] = dataclasses.field(default_factory=list)

    def nest(self) -> None:
        """Fill ``self_s`` from how the (sorted) ops nest."""
        self.self_s = [o.seconds for o in self.ops]
        stack: List[int] = []
        for i, o in enumerate(self.ops):
            while stack and self.ops[stack[-1]].end <= o.start:
                stack.pop()
            if stack:
                self.self_s[stack[-1]] -= o.seconds
            stack.append(i)

@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Device]
    host: List[Span]         # harness annotations (names given by prefix)


def _close_paren(s: str) -> int:
    """Index of the parenthesis closing the one that opens ``s``."""
    depth = 0
    for i, ch in enumerate(s):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return i
    return len(s) - 1


def _split(text: str) -> Tuple[str, str, str, str]:
    """(name, result type, opcode, operand list) of an instruction."""
    name, _, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    if rest.startswith("("):             # tuple type
        i = _close_paren(rest) + 1
        rtype, after = rest[:i], rest[i:]
    else:
        rtype, _, after = rest.partition(" ")
    m = re.match(r"\s*([a-z][a-z0-9\-]*)(?=\()", after)
    if not m:
        return name, rtype, "", ""
    args = after[m.end():]
    return name, rtype, m.group(1), args[:_close_paren(args) + 1]


def parse_instruction(text: str) -> Tuple[str, str]:
    """(instruction name, opcode) of an HLO instruction's text."""
    name, _, opcode, _ = _split(text)
    return name, opcode


def load(path: str, host_prefixes: Sequence[str] = ("bench.",)) -> Trace:
    """Read an ``.xplane.pb`` file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, Device] = {}
    host: List[Span] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device([])
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    end = start + ev.duration_ns * 1e-9
                    name, opcode = parse_instruction(ev.name)
                    dev.ops.append(Op(name, opcode, start, end, ev.name))
            dev.ops.sort(key=lambda o: (o.start, -o.end))
            dev.nest()
            devices[int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tuple(host_prefixes)):
                        start = ev.start_ns * 1e-9
                        host.append(Span(ev.name, start,
                                         start + ev.duration_ns * 1e-9))
    host.sort(key=lambda s: s.start)
    return Trace(devices, host)


# -- interval arithmetic -------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (sorted, disjoint) intervals ``a`` not covered by the
    (sorted, disjoint) intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# -- reductions ----------------------------------------------------------------

def window(trace: Trace, name: str) -> Interval:
    """The [start, end) of the first host annotation called ``name``."""
    for s in trace.host:
        if s.name == name:
            return s.start, s.end
    raise KeyError(f"no host annotation {name!r} in the trace")


def busy(dev: Device, lo: float, hi: float) -> List[Interval]:
    return union(clip(((o.start, o.end) for o in dev.ops), lo, hi))


def gaps(dev: Device, lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy(dev, lo, hi))


def name_gap(trace: Trace, gap: Interval) -> str:
    """The innermost harness annotation covering the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for s in trace.host:
        if s.start <= mid < s.end and (best is None or
                                       s.end - s.start < best.end - best.start):
            best = s
    return best.name if best else "(no annotation)"


def op_breakdown(trace: Trace, lo: float, hi: float, top: int = 10
                 ) -> List[List]:
    """Device seconds by operation class, each op's own time (nested ops
    not counted twice), averaged over devices; ops that start in
    [lo, hi)."""
    acc: Dict[str, float] = defaultdict(float)
    n = max(len(trace.devices), 1)
    for dev in trace.devices.values():
        for o, own in zip(dev.ops, dev.self_s):
            if lo <= o.start < hi:
                acc[o.label] += own / n
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            [:top]]


def gap_breakdown(trace: Trace, lo: float, hi: float, top: int = 10
                  ) -> List[List]:
    """Idle seconds by what the host was doing, averaged over devices."""
    acc: Dict[str, float] = defaultdict(float)
    n = max(len(trace.devices), 1)
    for dev in trace.devices.values():
        for g in gaps(dev, lo, hi):
            acc[name_gap(trace, g)] += (g[1] - g[0]) / n
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])
            [:top]]


def idle_share(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """Share of [lo, hi) in which no operation runs, averaged over the
    devices; None for a trace without devices."""
    devs = list(trace.devices.values())
    if not devs:
        return None
    return sum(1.0 - total(busy(d, lo, hi)) / (hi - lo)
               for d in devs) / len(devs)
