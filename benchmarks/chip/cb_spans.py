#!/usr/bin/env python3
"""The program's own spans in a profiler trace, beside the benchmark's.

``cb_trace.load`` keeps the benchmark's ``bench.`` spans.  ``load`` here
keeps the same trace and adds the program's ``repro.`` spans
(``src/repro/obs/spans.py``) with their numeric args.  ``cb_trace.reduce``
then gives each idle gap of the device to the innermost span of either
open in the gap's middle; ``idle_by_span`` cuts each gap where spans begin
and end, for gaps that run across several host steps; ``span_args`` sums
the args by span name.

    python benchmarks/chip/cb_spans.py --workload <cell> --seed <n>

runs one cell's set-up, traces its first ``TRACE_SECONDS`` of steps as a
``--trace 1`` run does, then steps ``UNTRACED_SECONDS`` with the profiler
off, and prints one JSON line: over the traced window and over the steps
whose device operations the trace still holds, the device's idle time per
step under each innermost span and the bytes the spans copied; the cost
of a span; the step counts with and without the profiler.  Like
``run.py``, it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import shutil
import tempfile
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import cb_harness
import cb_trace

PROGRAM_PREFIX = "repro."
NO_SPAN = "(no benchmark span)"
#: seconds of steps with the profiler off, for the step rate it is
#: compared with
UNTRACED_SECONDS = 20.0


@dataclasses.dataclass
class Span(cb_trace.Event):
    args: Dict[str, float] = dataclasses.field(default_factory=dict)


def load(path: str) -> cb_trace.Trace:
    """``cb_trace.load``'s trace, with the program's spans among its
    spans."""
    from jax.profiler import ProfileData

    trace = cb_trace.load(path)
    for plane in ProfileData.from_file(path).planes:
        if cb_trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            trace.spans.extend(
                Span(e.name, e.start_ns, e.duration_ns,
                     args={k: v for k, v in e.stats
                           if isinstance(v, (int, float))})
                for e in line.events if e.name.startswith(PROGRAM_PREFIX))
    return trace


def window_of(trace: cb_trace.Trace) -> Tuple[float, float]:
    for s in trace.spans:
        if s.name == cb_trace.WINDOW_SPAN:
            return s.start_ns, s.end_ns
    raise ValueError(f"the trace has no {cb_trace.WINDOW_SPAN} span")


def _innermost(spans: List[cb_trace.Event], t0: float, t1: float
               ) -> List[Tuple[float, float, str]]:
    """``[t0, t1]`` cut into pieces ``(lo, hi, name)``, each under one
    innermost span.  The spans come from one thread, so they nest."""
    out: List[Tuple[float, float, str]] = []
    stack: List[cb_trace.Event] = []
    t = t0

    def upto(end: float) -> None:
        nonlocal t
        name = stack[-1].name if stack else NO_SPAN
        lo, hi = max(t, t0), min(end, t1)
        if hi > lo:
            out.append((lo, hi, name))
        t = max(t, end)

    for s in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= s.start_ns:
            upto(stack[-1].end_ns)
            stack.pop()
        upto(s.start_ns)
        stack.append(s)
    while stack:
        upto(stack[-1].end_ns)
        stack.pop()
    upto(t1)
    return out


def idle_by_span(trace: cb_trace.Trace,
                 window: Optional[Tuple[float, float]] = None
                 ) -> Dict[str, float]:
    """Idle seconds of the device by the innermost span open, averaged over
    the devices.  ``cb_trace.reduce`` gives each idle gap whole to the span
    open in its middle; here a gap is cut where spans begin and end, so a
    gap that runs from one host step into the next is shared between
    them."""
    t0, t1 = window if window is not None else window_of(trace)
    pieces = _innermost([s for s in trace.spans
                         if s.name != cb_trace.WINDOW_SPAN], t0, t1)
    out: Dict[str, float] = defaultdict(float)
    for events in trace.ops.values():
        busy = cb_trace._union([(max(e.start_ns, t0), min(e.end_ns, t1))
                                for e in events if e.end_ns > t0
                                and e.start_ns < t1])
        idle, prev = [], t0
        for lo, hi in busy + [(t1, t1)]:
            if lo > prev:
                idle.append((prev, lo))
            prev = max(prev, hi)
        i = 0
        for lo, hi, name in pieces:
            while i < len(idle) and idle[i][1] <= lo:
                i += 1
            j = i
            while j < len(idle) and idle[j][0] < hi:
                out[name] += (min(hi, idle[j][1]) - max(lo, idle[j][0])) / 1e9
                j += 1
    return {k: v / len(trace.ops) for k, v in out.items()}


def span_args(trace: cb_trace.Trace,
              window: Optional[Tuple[float, float]] = None
              ) -> Dict[str, Dict[str, float]]:
    """Sum of each numeric arg, by span name, over the spans that start in
    the window (by default the ``bench.window`` span)."""
    t0, t1 = window if window is not None else window_of(trace)
    out: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for s in trace.spans:
        if t0 <= s.start_ns < t1:
            for k, v in getattr(s, "args", {}).items():
                out[s.name][k] += v
    return {name: dict(args) for name, args in out.items()}


def _count(trace: cb_trace.Trace, name: str,
           window: Tuple[float, float]) -> int:
    return sum(1 for s in trace.spans if s.name == name
               and window[0] <= s.start_ns and s.end_ns <= window[1])


def held_until(trace: cb_trace.Trace, window: Tuple[float, float]) -> float:
    """End of the last ``bench.step`` before the first one in which no
    device holds an operation: the device trace keeps a bounded number of
    events, and a part it dropped would read as idle time."""
    starts = [sorted(e.start_ns for e in evs) for evs in trace.ops.values()]
    end = window[0]
    for s in sorted((s for s in trace.spans if s.name == "bench.step"
                     and window[0] <= s.start_ns and s.end_ns <= window[1]),
                    key=lambda s: s.start_ns):
        if not all(bisect.bisect_left(st, s.start_ns)
                   < bisect.bisect_left(st, s.end_ns) for st in starts):
            break
        end = s.end_ns
    return end


def part(trace: cb_trace.Trace, window: Tuple[float, float]) -> dict:
    """One stretch of the traced window, whole steps only: the device's
    idle time per step under each innermost span (``idle_by_span``), the
    spans' args per step, and the per-layer numbers the program's spans
    give: device-idle milliseconds while a span is innermost, per
    ``plan_many`` call for the planner's spans and per repair for the
    store's and the copies', and MiB copied per repair, each way between
    host and device and into the store's concatenated arrays."""
    steps = _count(trace, "bench.step", window)
    if not steps:
        return {"steps": 0}
    idle = idle_by_span(trace, window)
    args = span_args(trace, window)
    calls = _count(trace, "bench.plan_many", window)
    repairs = _count(trace, "bench.execute_plan", window)
    numbers = {}
    if repairs:
        for name, key in (("store_concat_ms", "repro.store.concat"),
                          ("store_node_ms", "repro.store.node"),
                          ("gf_h2d_ms", "repro.gf.h2d"),
                          ("gf_d2h_ms", "repro.gf.d2h")):
            numbers[name] = idle.get(key, 0.0) * 1e3 / repairs
        for name, key in (("gf_h2d_MiB", "repro.gf.h2d"),
                          ("gf_d2h_MiB", "repro.gf.d2h"),
                          ("store_concat_MiB", "repro.store.concat")):
            numbers[name] = args.get(key, {}).get("bytes", 0.0) / 2**20 \
                / repairs
    elif calls:
        for p in ("prep", "dispatch", "fetch"):
            numbers[f"planner_{p}_ms"] = \
                idle.get(f"repro.plan.{p}", 0.0) * 1e3 / calls
    return {
        "window_s": (window[1] - window[0]) / 1e9, "steps": steps,
        "plan_calls": calls, "repairs": repairs,
        "busy_s": cb_trace.reduce(trace, window).busy_s,
        "idle_ms_per_step": {k: v * 1e3 / steps
                             for k, v in sorted(idle.items())},
        "args_per_step": {k: {a: v / steps for a, v in d.items()}
                          for k, d in sorted(args.items())},
        "split": numbers,
    }


def edges(trace: cb_trace.Trace, name: str,
          window: Tuple[float, float]) -> dict:
    """Where the first device idles inside the spans called ``name``:
    seconds from each span's start to the first operation that starts in
    it (``lead``), from the last such operation's end to the span's end
    (``tail``), and how many spans hold no operation."""
    ops = sorted(next(iter(trace.ops.values()), []),
                 key=lambda e: e.start_ns)
    starts = [e.start_ns for e in ops]
    lead = tail = 0.0
    empty = 0
    for s in trace.spans:
        if s.name != name or not window[0] <= s.start_ns < window[1]:
            continue
        i, j = (bisect.bisect_left(starts, s.start_ns),
                bisect.bisect_left(starts, s.end_ns))
        if i == j:
            empty += 1
            continue
        lead += (starts[i] - s.start_ns) / 1e9
        tail += max(0.0, s.end_ns - max(e.end_ns for e in ops[i:j])) / 1e9
    return {"lead_s": lead, "tail_s": tail, "spans_without_ops": empty}


def inactive_span_ns(count: int = 100_000) -> float:
    """Host nanoseconds to enter and leave one span with one arg while no
    profiler runs."""
    from repro.obs.spans import span
    t0 = time.perf_counter()
    for i in range(count):
        with span("cost", bytes=i):
            pass
    return (time.perf_counter() - t0) / count * 1e9


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from cb_traffic import Traffic

    bench, cell, config, mix = cb_harness.load_cell(args.workload)
    cb_harness.import_program()
    cb_harness.use_compile_cache()
    dev = cb_harness.accelerator(cell["chips"])[0]
    traffic = Traffic(config, mix, args.seed)
    traffic.setup(cb_harness.say)
    trace_dir = tempfile.mkdtemp(prefix="cb_spans_")
    try:
        t0 = time.perf_counter()
        cb_harness.measure(traffic, cb_harness.TRACE_SECONDS, trace_dir)
        traced_run_s = time.perf_counter() - t0
        trace = load(cb_trace.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    steps_traced = traffic.steps
    plain_s, _ = cb_harness.measure(traffic, UNTRACED_SECONDS, None)

    window = window_of(trace)
    held = (window[0], held_until(trace, window))
    summary = cb_trace.reduce(trace)
    rec = cb_harness.Run(traffic.traced, summary.window_s, 0.0,
                         dev.device_kind, summary)
    ops = [e for evs in trace.ops.values() for e in evs
           if window[0] <= e.start_ns < window[1]]
    line = {
        "workload": args.workload, "seed": args.seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "traced_run_s": traced_run_s,
        "untraced": {"window_s": plain_s,
                     "steps": traffic.steps - steps_traced},
        "device_ops": len(ops),
        "last_op_end_s": (max(e.end_ns for e in ops) - window[0]) / 1e9
        if ops else None,
        "window": part(trace, window),
        # the steps in which the device trace still holds operations
        "held": part(trace, held),
        # cb_trace.reduce's reading: each gap whole to its middle's span
        "idle_ms_per_step_by_middle": {
            k: v * 1e3 / steps_traced
            for k, v in sorted(summary.idle_gaps.items())},
        "spans_per_step": sum(
            1 for s in trace.spans if s.name.startswith(PROGRAM_PREFIX)
            and window[0] <= s.start_ns < window[1]) / steps_traced,
        "fetch_edges": edges(trace, "repro.plan.fetch", held),
        "d2h_edges": edges(trace, "repro.gf.d2h", held),
        "inactive_span_ns": inactive_span_ns(),
        # the accepted per-layer metrics, read from the whole traced part
        "per_layer": {m["name"]: cb_harness.reader(m["name"])(rec)
                      for m in cb_harness.metrics_of(bench, cell["name"],
                                                     True)},
    }
    t = traffic.traced
    if t.mm_shapes:
        line["gf_operand_MiB"] = sum(m * k + k * n for m, k, n in
                                     t.mm_shapes) / 2**20 / t.repairs
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
