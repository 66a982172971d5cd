"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

``load`` reads the device operations and the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names that start with ``bench.``) out of
the trace; ``reduce`` turns them into the busy time of the device inside
the measured window (the union of operation intervals), the device time of
each operation and program by name, and the idle gaps, each attributed to
the innermost host span that was open in its middle (with several workers,
the one of them that started last).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
#: the Pallas GF(2^8) kernel's custom call is named after its jitted
#: function; the planner's programs are the jitted kernels of jax_engine
KERNEL_OP = re.compile(r"^gf_matmul_pallas(\.\d+)?$")
PLANNER_PROGRAM = re.compile(r"jit__(star|fr|tr|ftr)_kernel\b")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    program: str = ""          # the XLA module an operation belongs to

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]        # device ordinal -> operations
    modules: Dict[int, List[Event]]    # device ordinal -> program runs
    spans: List[Event]                 # the benchmark's host spans


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the devices traced
    op_s: Dict[str, float]             # device s by "program/operation"
    program_s: Dict[str, float]        # device seconds by program name
    idle_gaps: Dict[str, float]        # idle seconds by the host span open

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, count: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:count]

    def top_gaps(self, count: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:count]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stat(event, key: str) -> str:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return ""


def op_name(text: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``fusion.3``: the device
    lines name an operation by its whole HLO instruction."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def program_name(text: str) -> str:
    """``jit__ftr_kernel(12)`` -> ``jit__ftr_kernel``."""
    return re.sub(r"\(\d+\)$", "", text.strip())


def _assign_programs(ops: List[Event], modules: List[Event]) -> None:
    """Give each operation without one the program whose run holds its
    start (programs run one after another on a device)."""
    mods = sorted(modules, key=lambda e: e.start_ns)
    i = 0
    for e in sorted(ops, key=lambda e: e.start_ns):
        while i < len(mods) and mods[i].end_ns <= e.start_ns:
            i += 1
        if not e.program and i < len(mods) and mods[i].start_ns <= e.start_ns:
            e.program = mods[i].name


def load(path: str) -> Trace:
    """Device operations, programs and the ``bench.`` host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = defaultdict(list)
    modules: Dict[int, List[Event]] = defaultdict(list)
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))].extend(
                    Event(op_name(e.name), e.start_ns, e.duration_ns,
                          program_name(_stat(e, "hlo_module")))
                    for e in line.events)
            elif m and line.name == MODULES_LINE:
                modules[int(m.group(1))].extend(
                    Event(program_name(e.name), e.start_ns, e.duration_ns)
                    for e in line.events)
            elif not m:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    for dev, events in ops.items():
        _assign_programs(events, modules.get(dev, []))
    return Trace(dict(ops), dict(modules), spans)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


class _OpenSpans:
    """The innermost host span open at increasing times.  One worker's
    spans come from one thread, so they nest, and a stack holds them.  With
    several workers the threads' spans overlap, and this is the span that
    started last among those still open."""

    def __init__(self, spans: List[Event]):
        self.spans = sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns))
        self.next = 0
        self.stack: List[Event] = []

    def at(self, t: float) -> str:
        while self.next < len(self.spans) and \
                self.spans[self.next].start_ns <= t:
            s = self.spans[self.next]
            while self.stack and self.stack[-1].end_ns <= s.start_ns:
                self.stack.pop()
            self.stack.append(s)
            self.next += 1
        while self.stack and self.stack[-1].end_ns <= t:
            self.stack.pop()
        return self.stack[-1].name if self.stack else "(no benchmark span)"


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None
           ) -> Summary:
    """Numbers of the window ``(start_ns, end_ns)``; by default the window
    is the ``bench.window`` span."""
    if window is None:
        w = [s for s in trace.spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError(f"the trace has no {WINDOW_SPAN} span")
        window = (w[0].start_ns, w[0].end_ns)
    t0, t1 = window
    if not trace.ops:
        raise ValueError("the trace has no device operations")
    inner = [s for s in trace.spans if s.name != WINDOW_SPAN]
    op_s: Dict[str, float] = defaultdict(float)
    program_s: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    busy = 0.0
    for dev, events in trace.ops.items():
        clipped = []
        for e in events:
            lo, hi = max(e.start_ns, t0), min(e.end_ns, t1)
            if hi <= lo:
                continue
            clipped.append((lo, hi))
            op_s[f"{e.program}/{e.name}"] += (hi - lo) / 1e9
        for e in trace.modules.get(dev, []):
            lo, hi = max(e.start_ns, t0), min(e.end_ns, t1)
            if hi > lo:
                program_s[e.name] += (hi - lo) / 1e9
        merged = _union(clipped)
        busy += sum(hi - lo for lo, hi in merged) / 1e9
        open_spans = _OpenSpans(inner)
        prev = t0
        for lo, hi in merged + [(t1, t1)]:
            if lo > prev:
                gaps[open_spans.at(0.5 * (prev + lo))] += (lo - prev) / 1e9
            prev = max(prev, hi)
    n = len(trace.ops)
    return Summary(window_s=(t1 - t0) / 1e9, busy_s=busy / n,
                   op_s={k: v / n for k, v in op_s.items()},
                   program_s={k: v / n for k, v in program_s.items()},
                   idle_gaps={k: v / n for k, v in gaps.items()})


def _op(key: str) -> Tuple[str, str]:
    program, _, name = key.rpartition("/")
    return program, name


def kernel_seconds(summary: Summary) -> float:
    """Device seconds of the GF(2^8) Pallas kernel."""
    return sum(s for key, s in summary.op_s.items()
               if KERNEL_OP.match(_op(key)[1]))


def planner_seconds(summary: Summary) -> float:
    """Device seconds of the jit planner's programs."""
    if summary.program_s:
        return sum(s for name, s in summary.program_s.items()
                   if PLANNER_PROGRAM.search(name))
    return sum(s for key, s in summary.op_s.items()
               if PLANNER_PROGRAM.search(_op(key)[0]))
