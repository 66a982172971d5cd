"""Coded data plane (ISSUE 10): reads and repairs as real transfers.

Closed forms and invariants:

* scenario validation rejects impossible fan-in and trace-without-plane;
* the default path (``dataplane=False``) emits none of the new summary
  keys (the bitwise golden guard pins the values themselves);
* a solo trace-driven read over constant-capacity links completes in
  exactly ``alpha / c`` seconds and moves ``fanin * alpha * block_bytes``
  bytes;
* trace arrivals with too few healthy endpoints are dropped and counted;
  endpoint failure mid-read tears the read down and banks exactly the
  partially transferred bytes;
* every completed repair's coded blocks decode (``can_reconstruct``) and
  a full ``reconstruct`` over k nodes round-trips the original file;
* wire-byte conservation: per repair, the done-fraction ledger sums to
  the plan totals for uninterrupted repairs and to strictly partial
  bytes for aborted segments, while ``work_accounting``'s
  banked + outstanding == plan-total triple holds at every epoch;
* chunked trace generation is chunk-size invariant;
* tracing a dataplane run never perturbs it, and the new event
  vocabulary round-trips through the Chrome converter and the report
  analyses (including the no-header ``repair_block`` fallback);
* the GF(2^8) kernel wrapper has no fallback: a Pallas error raises out
  of ``gf_matmul`` instead of being answered by another path.
"""
import dataclasses
import json
import math
import warnings

import numpy as np
import pytest

from repro.coding.gf import GF8
from repro.core import CodeParams
from repro.fleet import (FleetSimulator, FlexiblePolicy, ReadTrace,
                         Scenario, generate_trace, simulate)
from repro.obs.report import link_bytes, top_links_by_bytes
from repro.obs.trace import chrome_trace

PARAMS = CodeParams.msr(n=6, k=2, d=3, M=4.0)   # alpha=2; mini-store scale 1


def _const_caps(n: int, c: float):
    caps = np.full((n, n), c)
    np.fill_diagonal(caps, 0.0)
    return lambda rng, m: caps.copy()


# ---------------------------------------------------------------------------
# 1. Scenario validation
# ---------------------------------------------------------------------------

def test_fanin_must_not_exceed_live_helpers():
    with pytest.raises(ValueError, match="read_fanin"):
        Scenario(num_nodes=4, duration=10.0, dataplane=True, read_fanin=4)
    # same fan-in without the data plane stays legal (phantom reads never
    # transfer fragments, so the bound is a data-plane concern)
    Scenario(num_nodes=4, duration=10.0, read_fanin=4)


def test_read_trace_requires_dataplane():
    with pytest.raises(ValueError, match="read_trace"):
        Scenario(num_nodes=6, duration=10.0,
                 read_trace=ReadTrace(rate=1.0))


def test_read_trace_needs_exactly_one_source():
    with pytest.raises(ValueError):
        ReadTrace()
    with pytest.raises(ValueError):
        ReadTrace(path="x.jsonl", rate=1.0)


def test_dataplane_blocks_must_divide_by_k():
    sc = Scenario(num_nodes=6, duration=10.0, dataplane=True,
                  dataplane_blocks=5)
    with pytest.raises(ValueError, match="divisible"):
        FleetSimulator(sc, FlexiblePolicy(), PARAMS, seed=0)


def test_bad_matmul_mode_rejected():
    with pytest.raises(ValueError, match="dataplane_matmul"):
        Scenario(num_nodes=6, duration=10.0, dataplane=True,
                 dataplane_matmul="cuda")


# ---------------------------------------------------------------------------
# 2. Default path emits no dataplane keys
# ---------------------------------------------------------------------------

def test_default_path_has_no_dataplane_keys():
    sc = Scenario(num_nodes=6, duration=50.0, failure_rate=2e-3,
                  capacity_model=_const_caps(6, 4.0))
    summary = simulate(sc, FlexiblePolicy(), PARAMS, seed=0)
    for key in ("repair_bytes", "read_bytes", "reads_completed",
                "reads_dropped", "decode_checks", "read_p50", "read_p99"):
        assert key not in summary, key


# ---------------------------------------------------------------------------
# 3. Closed-form read latency and bytes
# ---------------------------------------------------------------------------

def test_solo_trace_read_closed_form(tmp_path):
    """One read, no contention: latency == alpha/c, bytes == fanin*alpha*bb."""
    p = tmp_path / "one.jsonl"
    p.write_text('{"t": 1.0}\n')
    sc = Scenario(num_nodes=6, duration=10.0, failure_rate=0.0,
                  capacity_model=_const_caps(6, 4.0), dataplane=True,
                  read_trace=ReadTrace(path=str(p)))
    m = FleetSimulator(sc, FlexiblePolicy(), PARAMS, seed=3).run()
    assert m.reads_completed == 1 and m.reads_dropped == 0
    # fanin = k = 2 fragments of alpha = 2 blocks over capacity-4 links
    assert m.read_latencies == [pytest.approx(2.0 / 4.0)]
    want_bytes = 2 * 2.0 * sc.dataplane_block_bytes
    assert m.read_bytes == pytest.approx(want_bytes)
    s = m.summary()
    assert s["read_p50"] == pytest.approx(0.5)
    assert s["read_p99"] == pytest.approx(0.5)


def test_trace_read_drop_when_too_few_healthy(tmp_path):
    """With fanin == len(healthy) - 0 endpoints free, arrivals drop."""
    p = tmp_path / "reads.jsonl"
    p.write_text('{"t": 2.0}\n')
    sc = Scenario(num_nodes=4, duration=60.0, failure_rate=0.0,
                  failures=((1.0, 0),),
                  capacity_model=_const_caps(4, 0.1), dataplane=True,
                  read_fanin=3, read_trace=ReadTrace(path=str(p)))
    m = FleetSimulator(sc, FlexiblePolicy(), PARAMS, seed=1).run()
    # the capacity-0.1 links keep node 0's repair running well past t=2.0,
    # so at the arrival 3 healthy == fanin and the read cannot pick fanin
    # sources plus a distinct destination -> dropped
    assert m.reads_dropped == 1 and m.reads_completed == 0


def test_endpoint_failure_tears_down_read_and_banks_partial(tmp_path):
    p = tmp_path / "reads.jsonl"
    p.write_text('{"t": 0.5}\n')
    sc = Scenario(num_nodes=4, duration=60.0, failure_rate=0.0,
                  failures=((1.0, 2),),
                  capacity_model=_const_caps(4, 0.5), dataplane=True,
                  read_fanin=3, read_trace=ReadTrace(path=str(p)))
    sim = FleetSimulator(sc, FlexiblePolicy(), PARAMS, seed=1)
    m = sim.run()
    # fanin=3 sources + 1 destination = all 4 nodes, so the t=1.0 failure
    # is always a read endpoint: the read tears down, never completes
    assert m.reads_torn_down == 1 and m.reads_completed == 0
    # solo nominal = alpha/c = 2/0.5 = 4s; 0.5s in -> done = 1/8 of each
    # of the 3 fragments' 2 blocks
    partial = (0.5 / 4.0) * 3 * 2.0 * sc.dataplane_block_bytes
    assert m.read_bytes == pytest.approx(partial)
    assert sum(sim.dataplane.read_link_bytes.values()) == \
        pytest.approx(partial)


# ---------------------------------------------------------------------------
# 4. Coded store: decode verification + full reconstruct round-trip
# ---------------------------------------------------------------------------

def test_repairs_decode_and_reconstruct_roundtrip():
    sc = Scenario(num_nodes=6, duration=400.0, failure_rate=0.0,
                  failures=((5.0, 0), (60.0, 3), (120.0, 1)),
                  capacity_model=_const_caps(6, 4.0), dataplane=True,
                  dataplane_verify=True)
    sim = FleetSimulator(sc, FlexiblePolicy(), PARAMS, seed=7)
    m = sim.run()
    assert m.completed == 3
    assert m.decode_checks == 3 and m.decode_failures == 0
    # the regenerated store still reconstructs the original file from k
    # nodes, including a regenerated one
    dp = sim.dataplane
    M = int(dp.mini.M)
    combo = [dp.store.nodes[i] for i in (0, 3)]     # both were regenerated
    got = dp.store.rl.reconstruct(combo, M)
    np.testing.assert_array_equal(got, dp.store.file_blocks)


def test_matmul_backends_agree():
    """The kernel-backed GF matmul must not change the coded store's
    results vs the log/antilog tables (same rng stream, same blocks)."""
    base = Scenario(num_nodes=6, duration=60.0, failure_rate=0.0,
                    failures=((5.0, 0),),
                    capacity_model=_const_caps(6, 4.0), dataplane=True,
                    dataplane_verify=True)
    stores = []
    for mode in ("numpy", "kernel"):
        sc = dataclasses.replace(base, dataplane_matmul=mode)
        sim = FleetSimulator(sc, FlexiblePolicy(), PARAMS, seed=7)
        sim.run()
        stores.append(sim.dataplane.store)
    for i in stores[0].nodes:
        np.testing.assert_array_equal(stores[0].nodes[i].vectors,
                                      stores[1].nodes[i].vectors)
        np.testing.assert_array_equal(stores[0].nodes[i].payload,
                                      stores[1].nodes[i].payload)


# ---------------------------------------------------------------------------
# 5. Wire-byte conservation
# ---------------------------------------------------------------------------

def test_uninterrupted_repair_bytes_equal_plan_total():
    """No aborts: the done-fraction ledger must sum to exactly the plan's
    per-link flows (the ``repair_block`` events carry those totals)."""
    sc = Scenario(num_nodes=6, duration=200.0, failure_rate=0.0,
                  failures=((5.0, 0),),
                  capacity_model=_const_caps(6, 4.0), dataplane=True,
                  trace=True)
    sim = FleetSimulator(sc, FlexiblePolicy(), PARAMS, seed=11)
    m = sim.run()
    assert m.completed == 1 and m.aborted == 0
    blocks = [e for e in sim.recorder.events if e["ev"] == "repair_block"]
    assert blocks
    assert m.repair_bytes == pytest.approx(sum(e["bytes"] for e in blocks))


def test_bytes_conserved_across_aborts_and_carryover():
    """Per-repair ledger == plan total for completed-clean repairs,
    strictly partial for aborted segments; global ledger == the sum; and
    the banked + outstanding == plan-total triple holds every epoch."""
    caps = np.full((8, 8), 2.0)
    np.fill_diagonal(caps, 0.0)
    sc = Scenario(num_nodes=8, duration=600.0, failure_rate=0.0,
                  failures=((1.0, 0), (4.0, 1), (8.0, 2)),
                  capacity_model=lambda rng, m: caps.copy(),
                  carryover=True, trace=True)
    params = CodeParams.msr(n=8, k=2, d=4, M=40.0)
    sc = dataclasses.replace(sc, dataplane=True)
    sim = FleetSimulator(sc, FlexiblePolicy(), params, seed=5)
    per_rid = {}
    orig = sim.dataplane.account_repair_wire

    def spy(r, done):
        if done > 0.0:
            per_rid[r.rid] = per_rid.get(r.rid, 0.0) + \
                done * sum(f for _, f in r.links) * sim.dataplane.block_bytes
        orig(r, done)

    sim.dataplane.account_repair_wire = spy
    sim.start()
    while True:
        for r in sim.active:
            for link, (banked, out, total) in r.work_accounting().items():
                assert banked + out == pytest.approx(total), (r.rid, link)
        if not sim.step():
            break
    m = sim.finish()
    assert m.completed >= 2 and m.repair_bytes > 0
    assert m.repair_bytes == pytest.approx(sum(per_rid.values()))
    events = sim.recorder.events
    aborted = {e["rid"] for e in events if e["ev"] == "repair_abort"}
    for e in events:
        if e["ev"] != "repair_complete":
            continue
        rid = e["rid"]
        plan_total = sum(b["bytes"] for b in events
                         if b["ev"] == "repair_block" and b["rid"] == rid)
        if rid in aborted:
            # carryover: banked blocks are never re-sent, so the wire
            # moved strictly less than the full plan
            assert 0.0 < per_rid[rid] < plan_total + 1e-6, rid
        else:
            assert per_rid[rid] == pytest.approx(plan_total), rid


# ---------------------------------------------------------------------------
# 6. Trace generation
# ---------------------------------------------------------------------------

def test_generate_trace_chunk_invariant(tmp_path):
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    n1 = generate_trace(str(a), rate=5.0, duration=40.0, seed=9, chunk=8)
    n2 = generate_trace(str(b), rate=5.0, duration=40.0, seed=9,
                        chunk=65536)
    assert n1 == n2 > 100
    assert a.read_text() == b.read_text()
    ts = [json.loads(ln)["t"] for ln in a.read_text().splitlines()]
    assert len(ts) == n1
    assert all(x < y for x, y in zip(ts, ts[1:]))
    assert ts[-1] <= 40.0


def test_generate_trace_validates_inputs(tmp_path):
    with pytest.raises(ValueError):
        generate_trace(str(tmp_path / "x.jsonl"), rate=0.0, duration=1.0)
    with pytest.raises(ValueError):
        generate_trace(str(tmp_path / "x.jsonl"), rate=1.0, duration=0.0)


# ---------------------------------------------------------------------------
# 7. Observability: vocabulary, traced == untraced, report analyses
# ---------------------------------------------------------------------------

def _dataplane_scenario(trace: bool, tmp_path) -> Scenario:
    p = tmp_path / "w.jsonl"
    if not p.exists():
        generate_trace(str(p), rate=0.05, duration=300.0, seed=2)
    return Scenario(num_nodes=6, duration=300.0, failure_rate=0.0,
                    failures=((5.0, 0), (90.0, 3)),
                    capacity_model=_const_caps(6, 4.0), dataplane=True,
                    dataplane_verify=True, trace=trace,
                    read_trace=ReadTrace(path=str(p)))


def test_tracing_never_perturbs_the_dataplane(tmp_path):
    untraced = FleetSimulator(_dataplane_scenario(False, tmp_path),
                              FlexiblePolicy(), PARAMS, seed=13).run()
    traced_sim = FleetSimulator(_dataplane_scenario(True, tmp_path),
                                FlexiblePolicy(), PARAMS, seed=13)
    traced = traced_sim.run()
    assert traced.summary() == untraced.summary()
    kinds = {e["ev"] for e in traced_sim.recorder.events}
    assert {"read_queued", "read_complete", "repair_block"} <= kinds


def test_chrome_and_report_round_trip(tmp_path):
    sim = FleetSimulator(_dataplane_scenario(True, tmp_path),
                         FlexiblePolicy(), PARAMS, seed=13)
    m = sim.run()
    sim.finish()
    assert m.reads_completed > 0
    trace = sim.recorder.to_chrome()
    reads_closed = [e for e in trace["traceEvents"]
                    if e.get("ph") == "e" and e.get("cat") == "read"
                    and e.get("args", {}).get("reason") == "complete"]
    assert len(reads_closed) == m.reads_completed
    # read spans must never pollute the repair category (check_trace.py
    # counts cat=="repair" ends against completed + aborted)
    assert all(e.get("cat") != "repair" for e in reads_closed)
    header, events = sim.recorder.header(), sim.recorder.events
    top = top_links_by_bytes(header, events, 5)
    assert top
    assert header["meta"]["dataplane"]["links"]
    best = top[0][1]
    assert best["repair_bytes"] + best["read_bytes"] > 0
    # fallback path: no header snapshot -> repair bytes re-summed from
    # the repair_block events themselves
    fb = link_bytes({"meta": {}}, events)
    assert fb
    want = sum(e["bytes"] for e in events if e["ev"] == "repair_block")
    assert sum(c["repair_bytes"] for c in fb.values()) == \
        pytest.approx(want)


# ---------------------------------------------------------------------------
# 8. No kernel fallback
# ---------------------------------------------------------------------------

def test_gf_matmul_propagates_kernel_errors(monkeypatch):
    from repro.kernels import ops

    rng = np.random.default_rng(42)
    a = rng.integers(0, 256, (5, 7), dtype=np.uint8)
    b = rng.integers(0, 256, (7, 9), dtype=np.uint8)

    def boom(*args, **kwargs):
        raise RuntimeError("kernel rejected by the backend's compiler")

    monkeypatch.setattr(ops, "_padded_call", boom)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for _ in range(2):          # every call raises: nothing latches
            with pytest.raises(RuntimeError, match="kernel rejected"):
                ops.gf_matmul(a, b)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(ops.gf_matmul(a, b)),
                                  GF8.matmul(a, b))
