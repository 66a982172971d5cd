"""The program's ``repro.`` spans nested inside the benchmark's ``bench.``
spans, on a synthetic trace: each idle gap goes to the innermost span, the
spans' args sum, and every accepted metric reads the same with the
program's spans in the trace as without them."""
import dataclasses
import os
from collections import defaultdict

import pytest

import cb_harness
import cb_spans
import cb_trace


def _ev(name, start, dur, program=""):
    return cb_trace.Event(name, float(start), float(dur), program)


def _span(name, start, end, **args):
    return cb_spans.Span(name, float(start), float(end - start), args=args)


BENCH = [_ev("bench.window", 0, 2000), _ev("bench.step", 0, 2000),
         _ev("bench.plan_many", 100, 400),
         _ev("bench.execute_plan", 600, 1200),
         _ev("bench.gf_matmul", 800, 400)]
# a plan, then a repair: a helper's node, with a GF call and a concat,
# inside the newcomer's node; the last copy starts after the window
PROGRAM = [_span("repro.plan_many", 110, 480),
           _span("repro.plan.prep", 110, 150),
           _span("repro.plan.dispatch", 150, 200),
           _span("repro.plan.fetch", 200, 480),
           _span("repro.execute_plan", 610, 1790),
           _span("repro.store.node", 610, 1790),
           _span("repro.store.node", 700, 1500),
           _span("repro.gf_matmul", 810, 1190),
           _span("repro.gf.h2d", 810, 900, bytes=3000),
           _span("repro.gf.dispatch", 900, 950),
           _span("repro.gf.d2h", 950, 1190, bytes=1000),
           _span("repro.store.concat", 1300, 1400),
           _span("repro.store.concat", 1600, 1700),
           _span("repro.gf.h2d", 2100, 2110, bytes=7)]
# the device's idle gaps, the innermost span over each, and the benchmark
# span over it
GAPS = [(20, 40, "bench.step", "bench.step"),
        (120, 140, "repro.plan.prep", "bench.plan_many"),
        (160, 190, "repro.plan.dispatch", "bench.plan_many"),
        (420, 470, "repro.plan.fetch", "bench.plan_many"),
        (485, 495, "bench.plan_many", "bench.plan_many"),
        (605, 608, "bench.execute_plan", "bench.execute_plan"),
        (620, 680, "repro.store.node", "bench.execute_plan"),
        (710, 790, "repro.store.node", "bench.execute_plan"),
        (802, 808, "bench.gf_matmul", "bench.gf_matmul"),
        (820, 880, "repro.gf.h2d", "bench.gf_matmul"),
        (910, 940, "repro.gf.dispatch", "bench.gf_matmul"),
        (1000, 1180, "repro.gf.d2h", "bench.gf_matmul"),
        (1192, 1198, "bench.gf_matmul", "bench.gf_matmul"),
        (1320, 1380, "repro.store.concat", "bench.execute_plan"),
        (1620, 1680, "repro.store.concat", "bench.execute_plan"),
        (1795, 1798, "bench.execute_plan", "bench.execute_plan")]


def _ops():
    """Device operations everywhere but the gaps: the planner's program
    before the repair, the GF kernel in it, one operation past the end."""
    ops, prev = [], 0
    for lo, hi, _, _ in GAPS + [(2050, 2050, "", "")]:
        name, prog = (("while.3", "jit__ftr_kernel") if prev < 600 else
                      ("gf_matmul_pallas.1", "jit__padded_call"))
        ops.append(_ev(name, prev, lo - prev, prog))
        prev = hi
    return ops


def _trace(program):
    return cb_trace.Trace({0: _ops()}, {},
                          BENCH + (PROGRAM if program else []))


def _expected(column):
    out = defaultdict(float)
    for g in GAPS:
        out[g[column]] += (g[1] - g[0]) / 1e9
    return dict(out)


def test_idle_gaps_go_to_the_innermost_span():
    s = cb_trace.reduce(_trace(True))
    assert s.idle_gaps == pytest.approx(_expected(2))
    assert cb_trace.reduce(_trace(False)).idle_gaps == \
        pytest.approx(_expected(3))
    # each gap lies under one span, so cutting gaps changes nothing
    assert cb_spans.idle_by_span(_trace(True)) == pytest.approx(_expected(2))
    assert cb_spans.idle_by_span(_trace(False)) == \
        pytest.approx(_expected(3))


def test_a_gap_across_host_steps_is_cut_where_they_meet():
    # the kernel ends, its product comes back, the store concatenates and
    # the next operands go up before the device runs again: one gap
    ops = [_ev("gf_matmul_pallas.1", 0, 100), _ev("pad.2", 900, 100)]
    spans = [_ev("bench.window", 0, 1000), _ev("bench.step", 0, 1000),
             _span("repro.gf.d2h", 50, 300), _span("repro.store.concat",
                                                   300, 600),
             _span("repro.gf.h2d", 600, 950)]
    trace = cb_trace.Trace({0: ops, 1: ops[:1]}, {}, spans)
    assert cb_trace.reduce(trace).idle_gaps == pytest.approx(
        {"repro.store.concat": (800e-9 + 900e-9) / 2})
    # device 1 idles from 100 to the end; each device's idle time counts
    # half
    assert cb_spans.idle_by_span(trace) == pytest.approx(
        {"repro.gf.d2h": 200e-9, "repro.store.concat": 300e-9,
         "repro.gf.h2d": (300e-9 + 350e-9) / 2, "bench.step": 50e-9 / 2})


def test_span_args_sum_over_the_window():
    assert cb_spans.span_args(_trace(True)) == {
        "repro.gf.h2d": {"bytes": 3000.0}, "repro.gf.d2h": {"bytes": 1000.0}}
    assert cb_spans.span_args(_trace(True), window=(0, 3000))[
        "repro.gf.h2d"] == {"bytes": 3007.0}
    assert cb_spans.span_args(_trace(False)) == {}


@dataclasses.dataclass
class _Traffic:
    """What the accepted readers read of a window: two plan calls and one
    repair of three GF calls."""
    steps: int = 2
    repairs: int = 1
    plan_calls: tuple = (("ftr", 1, 0.4e-6), ("ftr", 2, 0.3e-6))
    exec_s: tuple = (1.2e-6,)
    exec_mm_s: tuple = (0.4e-6,)
    mm_shapes: tuple = ((43, 128, 1 << 20), (43, 128, 1 << 20),
                        (128, 344, 1 << 20))
    mix: dict = dataclasses.field(default_factory=lambda: {"repair": True})
    config: dict = dataclasses.field(default_factory=lambda: {
        "alpha": 128, "cell_bytes": 1 << 20})

    def plans_returned(self):
        return sum(b for _, b, _ in self.plan_calls)


def test_accepted_metrics_read_the_same_with_the_program_spans():
    without = cb_trace.reduce(_trace(False))
    with_ = cb_trace.reduce(_trace(True))
    for field in ("window_s", "busy_s", "op_s", "program_s"):
        assert getattr(with_, field) == getattr(without, field)
    assert cb_trace.kernel_seconds(with_) == cb_trace.kernel_seconds(without)
    assert cb_trace.planner_seconds(with_) == \
        cb_trace.planner_seconds(without)
    bench = cb_harness.load_json(os.path.join(cb_harness.ROOT,
                                              "BENCHMARK.json"))
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    for name in names:
        a, b = (cb_harness.reader(name)(cb_harness.Run(
            _Traffic(), 2e-6, 30.0, "TPU v5 lite", s,
            say=lambda *a, **k: None)) for s in (without, with_))
        assert a is not None and a == b, name


def test_part_of_the_window_and_the_steps_the_device_trace_holds():
    p = cb_spans.part(_trace(True), (0, 2000))
    assert (p["steps"], p["plan_calls"], p["repairs"]) == (1, 1, 1)
    assert p["split"] == pytest.approx({
        "store_concat_ms": 120e-6, "store_node_ms": 140e-6,
        "gf_h2d_ms": 60e-6, "gf_d2h_ms": 180e-6,
        "gf_h2d_MiB": 3000 / 2**20, "gf_d2h_MiB": 1000 / 2**20,
        "store_concat_MiB": 0.0})
    # the planner's fetch: the device idles from its start until an
    # operation starts at 470, which runs past its end
    assert cb_spans.edges(_trace(True), "repro.plan.fetch", (0, 2000)) == \
        pytest.approx({"lead_s": 270e-9, "tail_s": 0.0,
                       "spans_without_ops": 0})
    # three steps; the device trace holds no operation after the second
    ops = [_ev("while.3", 10, 50), _ev("while.3", 110, 50)]
    steps = [_ev("bench.window", 0, 300)] + [
        _ev("bench.step", 100 * i, 100) for i in range(3)]
    trace = cb_trace.Trace({0: ops}, {}, steps)
    assert cb_spans.held_until(trace, (0, 300)) == 200
    assert cb_spans.held_until(cb_trace.Trace({0: ops[1:]}, {}, steps),
                               (0, 300)) == 0
