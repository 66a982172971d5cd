"""The benchmark's yardstick: peaks, GF work, GF table arithmetic, the
reference planner and the trace reduction."""
import random

import numpy as np
import pytest

import cb_gf
import cb_planref
import cb_roofline
import cb_trace


def test_gf_work_of_a_hand_worked_shape():
    # 2 x 3 times 3 x 4: 24 GF multiply-adds, 64 binary multiply-adds each,
    # two int8 operations per multiply-add; bytes 6 + 12 + 8
    assert cb_roofline.gf_matmul_work(2, 3, 4) == (2 * 64 * 24, 26)
    peak = cb_roofline.peaks("TPU v5 lite")
    t, bound = cb_roofline.least_time([(43, 128, 1 << 20)], peak)
    assert bound == "compute"
    assert t == pytest.approx(128 * 43 * 128 * (1 << 20) / 393e12)
    t, bound = cb_roofline.least_time([(1, 1, 1 << 20)], peak)
    assert bound == "memory"
    assert t == pytest.approx((1 + 2 * (1 << 20)) / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks known"):
        cb_roofline.peaks("cpu")


def _gf_mul_slow(a: int, b: int) -> int:
    out = 0
    while b:
        if b & 1:
            out ^= a
        a <<= 1
        if a & 0x100:
            a ^= cb_gf.POLY
        b >>= 1
    return out


def test_gf_tables_match_shift_and_add():
    rng = np.random.default_rng(1)
    for a, b in rng.integers(0, 256, size=(300, 2)):
        assert cb_gf.MUL[a, b] == _gf_mul_slow(int(a), int(b))
    nz = np.arange(1, 256)
    assert np.all(cb_gf.MUL[nz, cb_gf.INV[nz]] == 1)


def test_gf_matmul_rank_and_row_sums():
    rng = np.random.default_rng(2)
    a = rng.integers(0, 256, (5, 7), dtype=np.uint8)
    b = rng.integers(0, 256, (7, 64), dtype=np.uint8)
    c = cb_gf.matmul(a, b)
    for i, j in [(0, 0), (4, 63), (2, 17)]:
        want = 0
        for t in range(7):
            want ^= _gf_mul_slow(int(a[i, t]), int(b[t, j]))
        assert c[i, j] == want
    assert cb_gf.rank(a) == 5
    dependent = cb_gf.MUL[7, a[0]] ^ a[1]
    assert cb_gf.rank(np.vstack([a, dependent])) == 5
    assert cb_gf.rank(np.zeros((3, 3), np.uint8)) == 0
    # sums of columns commute with the product, masked or not, and see
    # one flipped byte
    for mask in (None, cb_gf.column_mask(rng, 64)):
        assert np.array_equal(cb_gf.row_sums(c, mask),
                              cb_gf.matmul(a, cb_gf.row_sums(b, mask)[:, None])
                              [:, 0])
    bad = c.copy()
    bad[3, 40] ^= 1
    assert cb_gf.row_sums(bad)[3] != cb_gf.row_sums(c)[3]
    odd = rng.integers(0, 256, (3, 13), dtype=np.uint8)
    assert np.array_equal(cb_gf.row_sums(odd),
                          np.bitwise_xor.reduce(odd, axis=1))


@pytest.mark.parametrize("scheme", ["star", "fr", "tr", "ftr"])
@pytest.mark.parametrize("nkd", [(5, 3, 4), (9, 6, 8)])
def test_reference_planner_agrees_with_the_scalar_oracle(scheme, nkd):
    from repro.core import CodeParams, OverlayNetwork, plan
    n, k, d = nkd
    ref = cb_planref.Planner(k, d, 4.0 * k)
    params = CodeParams.msr(n=n, k=k, d=d, M=4.0 * k)
    rng = random.Random(3)
    for _ in range(2):
        cap = [[0.0 if u == v else rng.uniform(10, 120)
                for v in range(d + 1)] for u in range(d + 1)]
        got = ref.plan(scheme, np.array(cap))
        want = plan(OverlayNetwork(cap), params, scheme, engine="scalar")
        assert got.parent == want.parent
        assert got.time == want.time and got.betas == want.betas
        assert got.traffic == want.total_traffic
        assert got.lower_bound == want.lower_bound


def test_float32_reference_departs_from_float64():
    cap = np.random.default_rng(4).uniform(10, 120, (9, 9))
    np.fill_diagonal(cap, 0.0)
    for scheme in ("star", "fr", "tr", "ftr"):
        a = cb_planref.Planner(6, 8, 768.0).plan(scheme, cap)
        b = cb_planref.Planner(6, 8, 768.0, np.float32).plan(scheme, cap)
        assert abs(float(b.time) - a.time) / (1 + a.time) > 1e-9


def _ev(name, start, dur, program=""):
    return cb_trace.Event(name, float(start), float(dur), program)


def test_trace_reduction_of_a_synthetic_trace():
    ops = [_ev("gf_matmul_pallas.1", 100, 50, "jit__padded_call"),
           _ev("pad.0", 140, 30, "jit__padded_call"),     # overlaps
           _ev("while.3", 300, 100, "jit__ftr_kernel"),
           _ev("fusion.9", 420, 20, "jit__ftr_kernel"),
           _ev("fusion.2", 950, 100, "jit__fr_kernel")]   # ends outside
    modules = [_ev("jit__padded_call(7)", 100, 70),
               _ev("jit__ftr_kernel(9)", 300, 140),
               _ev("jit__fr_kernel(3)", 950, 100)]
    spans = [_ev("bench.window", 0, 1000), _ev("bench.step", 0, 600),
             _ev("bench.plan_many", 250, 200),
             _ev("bench.execute_plan", 600, 200)]
    s = cb_trace.reduce(cb_trace.Trace({0: ops}, {0: modules}, spans))
    assert s.window_s == pytest.approx(1e-6)
    # busy: [100,170] + [300,400] + [420,440] + [950,1000] = 240 ns
    assert s.busy_s == pytest.approx(240e-9)
    assert s.idle_share == pytest.approx(1 - 0.24)
    assert cb_trace.kernel_seconds(s) == pytest.approx(50e-9)
    assert cb_trace.planner_seconds(s) == pytest.approx(190e-9)
    # gaps, by the span open in their middle: [0,100] and [170,300] in the
    # step, [400,420] in plan_many, [440,950] in execute_plan
    assert s.idle_gaps == pytest.approx({"bench.step": 230e-9,
                                         "bench.plan_many": 20e-9,
                                         "bench.execute_plan": 510e-9})
    assert s.top_ops(1)[0][0] == "jit__ftr_kernel/while.3"
    # without program events the planner time comes from the ops' programs
    s2 = cb_trace.reduce(cb_trace.Trace({0: ops}, {}, spans))
    assert cb_trace.planner_seconds(s2) == pytest.approx(170e-9)


def test_idle_gaps_of_overlapping_workers_go_to_the_latest_open_span():
    # two workers' steps overlap; each gap goes to the span that started
    # last among those open in its middle
    ops = [_ev("gf_matmul_pallas.1", 0, 100), _ev("gf_matmul_pallas.1",
                                                   300, 100),
           _ev("gf_matmul_pallas.1", 500, 100), _ev("while.3", 900, 100)]
    spans = [_ev("bench.window", 0, 1000),
             _ev("bench.step", 0, 800),             # worker 0
             _ev("bench.step", 50, 950),            # worker 1
             _ev("bench.gf_matmul", 120, 100),      # worker 0, [120, 220]
             _ev("bench.execute_plan", 420, 300)]   # worker 1, [420, 720]
    s = cb_trace.reduce(cb_trace.Trace({0: ops}, {}, spans))
    # [100,300]: middle 200 in worker 0's gf_matmul; [400,500] and
    # [600,900]: middles 450 and 750, worker 1's execute_plan, then, once
    # it has ended, worker 1's step, the later started of the two steps
    assert s.idle_gaps == pytest.approx({"bench.gf_matmul": 200e-9,
                                         "bench.execute_plan": 100e-9,
                                         "bench.step": 300e-9})


def test_trace_names_and_programs():
    assert cb_trace.op_name("%gf_matmul_pallas.1 = u8[128,1048576]{1,0} "
                            "custom-call(u8[128,512] %pad.0)") == \
        "gf_matmul_pallas.1"
    assert cb_trace.program_name("jit__ftr_kernel(12)") == "jit__ftr_kernel"
    ops = [_ev("a", 5, 1), _ev("b", 20, 1), _ev("c", 40, 1)]
    cb_trace._assign_programs(ops, [_ev("jit_p", 0, 10), _ev("jit_q", 15, 10)])
    assert [e.program for e in ops] == ["jit_p", "jit_q", ""]


def test_trace_reduction_needs_window_and_device():
    with pytest.raises(ValueError, match="bench.window"):
        cb_trace.reduce(cb_trace.Trace({0: []}, {}, []))
    with pytest.raises(ValueError, match="no device operations"):
        cb_trace.reduce(cb_trace.Trace({}, {}, [_ev("bench.window", 0, 9)]))
