"""The program's spans on a real profiler trace (CPU): their names and
nesting, the bytes the GF copies report, and that a span changes no result.

The trace is read back through the benchmark's own reader
(``benchmarks/chip/cb_spans.py``), so the names checked here are the names
the chip benchmark reads."""
import os
import sys
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.coding import GF8, RLNC
from repro.core import CodeParams, plan_many, plans_from_batch
from repro.kernels.ops import gf_matmul_numpy
from repro.storage.simulator import RlncSimulator

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip"))
import cb_spans  # noqa: E402
import cb_trace  # noqa: E402

PARAMS = CodeParams.msr(n=4, k=2, d=3, M=4.0)
FAILED, HELPERS = 0, [1, 2, 3]


def _caps(batch=2, seed=0):
    rng = np.random.default_rng(seed)
    caps = rng.uniform(10, 120, (batch, 4, 4))
    caps[:, np.arange(4), np.arange(4)] = 0.0
    return caps


def _store(shapes):
    """A coded store at n=4, k=2, d=3 whose matmul is the kernel, recording
    the shapes of its calls."""
    sim = RlncSimulator(PARAMS, block_bytes=16, seed=5)

    def matmul(a, b):
        shapes.append((a.shape[0], a.shape[1], b.shape[1]))
        return gf_matmul_numpy(a, b)

    sim.rl = RLNC(GF8, matmul=matmul)
    return sim


def _work(shapes, a, b):
    """One jax plan, one repair and two direct GF calls (host operands,
    and one operand already on the device)."""
    res = plan_many(_caps(), PARAMS, "ftr", engine="jax")
    sim = _store(shapes)
    sim.nodes.pop(FAILED)
    sim.execute_plan(plans_from_batch(res, PARAMS)[0], FAILED, HELPERS)
    c1 = gf_matmul_numpy(a, b)
    c2 = gf_matmul_numpy(jnp.asarray(a), b)
    return res, sim, c1, c2


@pytest.fixture(scope="module")
def traced():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 256, (3, 5), dtype=np.uint8)
    b = rng.integers(0, 256, (5, 40), dtype=np.uint8)
    plain = _work([], a, b)
    shapes = []
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            with jax.profiler.TraceAnnotation(cb_trace.WINDOW_SPAN):
                out = _work(shapes, a, b)
        trace = cb_spans.load(cb_trace.find_xplane(d))
    return trace, plain, out, shapes, a, b


def _named(trace, name):
    return sorted((s for s in trace.spans if s.name == name),
                  key=lambda s: s.start_ns)


def _inside(inner, outer):
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def _parent(trace, span):
    """The innermost other span that holds ``span``."""
    holders = [s for s in trace.spans if s is not span and _inside(span, s)]
    return min(holders, key=lambda s: s.dur_ns).name if holders else None


def test_span_names_and_nesting(traced):
    trace = traced[0]
    names = {s.name for s in trace.spans}
    assert {"repro.plan_many", "repro.plan.total", "repro.plan.prep",
            "repro.plan.dispatch", "repro.plan.fetch", "repro.execute_plan",
            "repro.store.node", "repro.store.concat", "repro.gf_matmul",
            "repro.gf.h2d", "repro.gf.dispatch", "repro.gf.d2h"} <= names
    assert len(_named(trace, "repro.plan_many")) == 1
    for part in ("prep", "dispatch", "fetch"):
        (s,) = _named(trace, f"repro.plan.{part}")
        assert _parent(trace, s) == "repro.plan.total"
    (total,) = _named(trace, "repro.plan.total")
    assert _parent(trace, total) == "repro.plan_many"
    prep, dispatch, fetch = (_named(trace, f"repro.plan.{p}")[0]
                             for p in ("prep", "dispatch", "fetch"))
    assert prep.end_ns <= dispatch.start_ns and dispatch.end_ns <= \
        fetch.start_ns
    (ex,) = _named(trace, "repro.execute_plan")
    nodes = _named(trace, "repro.store.node")
    # the newcomer and the three helpers, each a node of the tree
    assert len(nodes) == 4
    assert _parent(trace, nodes[0]) == "repro.execute_plan"
    assert all(_inside(n, nodes[0]) for n in nodes)
    for c in _named(trace, "repro.store.concat"):
        assert _parent(trace, c) == "repro.store.node"
    calls = _named(trace, "repro.gf_matmul")
    in_store = [c for c in calls if _inside(c, ex)]
    assert len(in_store) == len(traced[3]) and len(calls) == \
        len(in_store) + 2
    assert all(_parent(trace, c) == "repro.store.node" for c in in_store)
    for part in ("h2d", "dispatch", "d2h"):
        spans = _named(trace, f"repro.gf.{part}")
        assert len(spans) == len(calls)
        assert all(_parent(trace, s) == "repro.gf_matmul" for s in spans)


def test_copy_bytes_are_the_operands(traced):
    trace, _, _, shapes, a, b = traced
    h2d = [s.args["bytes"] for s in _named(trace, "repro.gf.h2d")]
    d2h = [s.args["bytes"] for s in _named(trace, "repro.gf.d2h")]
    want = [m * k + k * n for m, k, n in shapes]
    # the store's calls, then a call on host operands, then one whose first
    # operand is already on the device
    assert h2d == want + [a.nbytes + b.nbytes, b.nbytes]
    assert d2h == [m * n for m, _, n in shapes] + [3 * 40] * 2
    # a concat reports the bytes of the arrays it joins (rows of an M = 4
    # byte coding vector and a 16-byte payload) and how many parts it joins
    concat = [s.args for s in _named(trace, "repro.store.concat")]
    assert concat and all(a["bytes"] > 0 and a["bytes"] % (4 + 16) == 0
                          and a["parts"] >= 2 for a in concat)
    args = cb_spans.span_args(trace)
    assert args["repro.gf.h2d"]["bytes"] == sum(h2d)
    assert args["repro.gf.d2h"]["bytes"] == sum(d2h)
    assert "repro.store.node" not in args


def test_execute_plan_reports_rows_and_payload_bytes(traced):
    trace, _, (_, sim, _, _), shapes, _, _ = traced
    (ex,) = _named(trace, "repro.execute_plan")
    (rec,) = sim.reconstructions
    # payload operands are 16 bytes wide, coding-vector operands M = 4;
    # the newcomer's regenerate, the last call, reads every row it received
    payload = sum(k * n for _, k, n in shapes if n == 16)
    assert ex.args == {"rows": rec.rows_received,
                       "payload_bytes": rec.payload_bytes}
    assert rec.payload_bytes == payload and shapes[-1][1] == rec.rows_received
    assert rec.start_s < rec.end_s


def test_spans_change_no_result(traced):
    _, (res0, sim0, c10, c20), (res1, sim1, c11, c21), _, _, _ = traced
    for f in ("times", "traffic", "betas", "parents", "lower_bounds"):
        assert np.array_equal(getattr(res0, f), getattr(res1, f))
    assert sorted(sim0.nodes) == sorted(sim1.nodes)
    for i in sim0.nodes:
        assert np.array_equal(sim0.nodes[i].vectors, sim1.nodes[i].vectors)
        assert np.array_equal(sim0.nodes[i].payload, sim1.nodes[i].payload)
    assert np.array_equal(c10, c11) and np.array_equal(c20, c21)
    assert np.array_equal(c10, GF8.matmul(traced[4], traced[5]))
