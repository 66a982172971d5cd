"""Rehearsals of every traffic mix on the CPU at a tiny configuration, with
the interpreted kernel: the output check passes on the program and fails on
the control and on every planted fault, with one worker and with several;
the command refuses to measure without a TPU or without the system under
test."""
import argparse
import hashlib
import io
import json
import os
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import cb_check
import cb_control
import cb_harness
import cb_traffic

ROOT = cb_harness.ROOT
BENCH = cb_harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
#: n=4, k=2, d=3 at MSR, two 128-byte rows per node
TINY = {"name": "tiny", "n": 4, "k": 2, "d": 3, "cell_bytes": 128,
        "alpha": 2, "M": 4, "capacity_lo": 10.0, "capacity_hi": 120.0}
# one cell per mix: cells of one mix are alike at the tiny configuration
CELLS = {}
for _w in BENCH["workloads"]:
    CELLS.setdefault(_w["traffic"], _w["name"])
FAULTS = {"repair": ["control", "altered_answer", "half_batch",
                     "unchanged_state"],
          "admit": ["control", "altered_answer", "half_batch"],
          "plan-mc": ["control", "altered_answer", "half_batch"]}


SEED = 2**31 + 77


def rehearse(cell_name, program=None, seconds=0.3, seed=SEED, **mix_keys):
    import jax
    bench, cell, _, mix = cb_harness.load_cell(cell_name)
    mix = dict(mix, batch=[min(b, 8) for b in mix["batch"]], **mix_keys)
    args = argparse.Namespace(workload=cell_name, seed=seed, seconds=seconds,
                              trace=0)
    return cb_harness.run_on(args, bench, cell, TINY, mix, jax.devices(),
                             time.perf_counter(), program=program,
                             check_stream=io.StringIO())


@pytest.mark.parametrize("traffic", sorted(CELLS))
def test_mix_rehearsal_is_correct(traffic, capsys):
    line = rehearse(CELLS[traffic])
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    wanted = [m["name"] for m in cb_harness.metrics_of(
        BENCH, CELLS[traffic], trace=False)]
    assert sorted(line["metrics"]) == sorted(wanted)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    out = capsys.readouterr().out.splitlines()
    assert json.loads(out[-1]) == line
    window = [s for s in out if s.startswith("[window]")][0]
    assert "compiles_in_window=0 cache_loads_in_window=0" in window
    # the numbers compared come last in the result line
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("traffic,fault",
                         [(t, f) for t in sorted(FAULTS) for f in FAULTS[t]])
def test_control_and_faults_come_out_not_correct(traffic, fault):
    program = cb_control.PROGRAMS[fault](TINY)
    line = rehearse(CELLS[traffic], program=program)
    assert line["correct"] is False, line["checks"]
    assert line["failed"] > 0


def _window_line(out):
    fields = [s for s in out.splitlines() if s.startswith("[window]")][0]
    return dict(f.split("=", 1) for f in fields.split()[1:])


def test_two_workers_rehearsal_is_correct(capsys, monkeypatch):
    regenerated = []

    def data_file(seed, rows, cell, worker=0):
        regenerated.append(worker)
        return cb_traffic.data_file(seed, rows, cell, worker)

    monkeypatch.setattr(cb_check, "data_file", data_file)
    line = rehearse(CELLS["repair"], workers=2)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0
    # each worker's store is checked against its own block group
    assert regenerated == [0, 1]
    window = _window_line(capsys.readouterr().out)
    assert window["workers"] == "2"
    by_worker = [int(r) for r in window["repairs_by_worker"].split(",")]
    assert len(by_worker) == 2 and min(by_worker) > 0
    assert line["attempted"] == sum(by_worker) == int(window["repairs"])
    assert window["compiles_in_window"] == "0"


@pytest.mark.parametrize("fault", FAULTS["repair"])
def test_control_and_faults_at_two_workers_come_out_not_correct(fault):
    program = cb_control.PROGRAMS[fault](TINY)
    line = rehearse(CELLS["repair"], program=program, workers=2)
    assert line["correct"] is False, line["checks"]
    assert line["failed"] > 0


class _UnchangedInWorkerOne(cb_control.UnchangedState):
    """Repairs leave worker 1's store unchanged; worker 0's are sound."""

    def store(self, file, seed, matmul):
        if seed == cb_traffic.store_seed(SEED, 1):
            return super().store(file, seed, matmul)
        return cb_control.Program.store(self, file, seed, matmul)


def test_unchanged_state_in_one_workers_store_is_caught(capsys):
    line = rehearse(CELLS["repair"], program=_UnchangedInWorkerOne(TINY),
                    workers=2)
    by_worker = _window_line(capsys.readouterr().out)["repairs_by_worker"]
    assert int(by_worker.split(",")[1]) > 0
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["store_rows_bad"]["value"] > 0


class _AlteredPlansOfWorkerOne(cb_control.AlteredAnswer):
    """Worker 1's plans altered where they are produced; worker 0's plans
    and every GF product are sound."""

    def __init__(self, config):
        super().__init__(config)
        self.worker = threading.local()

    def plan(self, caps, scheme):
        if getattr(self.worker, "index", 0) == 1:
            return super().plan(caps, scheme)
        return cb_control.Program.plan(self, caps, scheme)

    kernel_matmul = cb_control.Program.kernel_matmul


def test_altered_plans_of_one_worker_are_each_compared(capsys, monkeypatch):
    program = _AlteredPlansOfWorkerOne(TINY)
    plan = cb_traffic.Traffic._plan

    def plan_of(self, caps, scheme, worker):
        program.worker.index = worker.index
        return plan(self, caps, scheme, worker)

    monkeypatch.setattr(cb_traffic.Traffic, "_plan", plan_of)
    line = rehearse(CELLS["repair"], program=program, workers=2)
    by_worker = _window_line(capsys.readouterr().out)["repairs_by_worker"]
    steps = int(by_worker.split(",")[1])
    assert steps > 0
    assert line["correct"] is False, line["checks"]
    # every draw worker 1 planned is compared, even where worker 0 planned
    # the same draw first
    draws = cb_harness.load_cell(CELLS["repair"])[3]["draws"]
    assert line["checks"]["invalid_plans"]["value"] == min(steps, draws)
    assert line["checks"]["kernel_bytes_wrong"]["value"] == 0


class _FakeProfiler:
    """The profiler's start, stop and spans, recording when it stopped."""

    def __init__(self):
        self.stopped = []

    def start_trace(self, trace_dir):
        pass

    def stop_trace(self):
        self.stopped.append(time.perf_counter())

    class TraceAnnotation:
        def __init__(self, name, **args):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False


def test_the_traced_part_of_two_workers_holds_whole_steps(monkeypatch):
    import jax
    profiler = _FakeProfiler()
    for name in ("start_trace", "stop_trace", "TraceAnnotation"):
        monkeypatch.setattr(jax.profiler, name, getattr(profiler, name))
    monkeypatch.setattr(cb_harness, "TRACE_SECONDS", 0.3)
    _, _, _, mix = cb_harness.load_cell(CELLS["repair"])
    t = cb_traffic.Traffic(TINY, dict(mix, workers=2), SEED)
    t.setup(lambda *a, **k: None)
    steps = []                         # (worker, start, end)
    step = cb_traffic.Traffic.step

    def slow_step(self, w):
        start = time.perf_counter()
        time.sleep(0.05 if w.index == 0 else 0.17)
        step(self, w)
        steps.append((w.index, start, time.perf_counter()))

    monkeypatch.setattr(cb_traffic.Traffic, "step", slow_step)
    _, t0 = cb_harness.measure(t, 0.8, "unused")
    assert len(profiler.stopped) == 1
    stop = profiler.stopped[0]
    traced = [s for s in steps if s[1] < stop]
    # no step is in flight when the profiler stops, every worker's traced
    # part reaches past TRACE_SECONDS, and the copy holds each traced step
    assert all(end <= stop for _, _, end in traced)
    for w in (0, 1):
        ends = [end for i, _, end in traced if i == w]
        assert max(ends) - t0 >= cb_harness.TRACE_SECONDS
        assert t.traced.worker_of["repaired"].count(w) == len(ends)
    assert t.traced.repairs == len(traced) == len(t.traced.exec_s)
    assert len(steps) > len(traced)


def test_records_of_more_workers_than_cores_lose_no_update():
    _, _, _, mix = cb_harness.load_cell(CELLS["repair"])
    t = cb_traffic.Traffic(TINY, dict(mix, workers=16), SEED)
    t.setup(lambda *a, **k: None)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        window = threading.Thread(target=cb_harness.measure,
                                  args=(t, 0.3, None))
        window.start()
        window.join(timeout=240)
    finally:
        sys.setswitchinterval(interval)
    assert not window.is_alive()
    steps = [w.steps for w in t.workers]
    assert min(steps) > 0 and t.steps == sum(steps) == len(t.plan_calls)
    for name in cb_traffic.RECORDS:
        assert len(getattr(t, name)) == len(t.worker_of[name]), name
    assert [t.worker_of["plan_calls"].count(w) for w in range(16)] == steps
    assert t.repairs_by_worker() == steps
    assert len(t.exec_s) == t.repairs == sum(steps)


def test_store_host_ms_leaves_out_the_repairs_in_flight():
    _, _, _, mix = cb_harness.load_cell(CELLS["repair"])
    t = cb_traffic.Traffic(TINY, dict(mix, workers=2), SEED)
    t.setup(lambda *a, **k: None)
    t.recording = True
    first, second = t.workers
    t.step(first)
    # the second worker's repair is in flight: one GF call done, a slow one
    a = np.ones((2, 4), np.uint8)
    b = np.ones((4, TINY["cell_bytes"]), np.uint8)
    t.program.kernel_matmul = lambda a, b, slow=t.program.kernel_matmul: (
        time.sleep(0.2), slow(a, b))[1]
    t.matmul(a, b, worker=second)
    run = cb_harness.Run(t, 1.0, 0.0, "TPU v5 lite")
    host_ms = cb_harness.reader("store_host_ms")(run)
    assert t.repairs == 1 and len(t.mm_shapes) > len(t.exec_mm_s)
    assert host_ms == pytest.approx((t.exec_s[0] - t.exec_mm_s[0]) * 1e3)
    assert 0 < host_ms < 200


@pytest.mark.parametrize("traffic,workers",
                         [("repair", 0), ("repair", 17), ("repair", 2.0),
                          ("admit", 2)])
def test_workers_out_of_range_are_refused_before_a_thread(
        traffic, workers, monkeypatch):
    def no_thread(self):
        raise AssertionError("a thread started")

    monkeypatch.setattr(threading.Thread, "start", no_thread)
    with pytest.raises(cb_harness.Refused, match="workers"):
        rehearse(CELLS[traffic], workers=workers)


def _store_bytes(h, nodes):
    for i in sorted(nodes):
        h.update(repr(i).encode())
        h.update(np.ascontiguousarray(nodes[i].vectors).tobytes())
        h.update(np.ascontiguousarray(nodes[i].payload).tobytes())


#: the digest below as the harness gave it before mixes had ``workers``:
#: a mix without the key keeps its random streams, stores and GF shapes
ONE_WORKER_DIGEST = \
    "3265fc5d39d336d5015539a4b89e4f8f933d276e386c790bdbc4bde7950728b2"


def test_a_mix_without_workers_keeps_its_draws_stores_and_shapes():
    _, _, _, mix = cb_harness.load_cell(CELLS["repair"])
    assert "workers" not in mix
    t = cb_traffic.Traffic(TINY, mix, SEED)
    t.setup(lambda *a, **k: None)
    assert len(t.workers) == 1
    worker = t.workers[0]
    h = hashlib.sha256()
    for caps, failed, helpers in t.draws:
        h.update(caps.tobytes())
        h.update(repr((failed, helpers)).encode())
    h.update(np.asarray(worker.order).tobytes())
    h.update(repr(t.warmed_shapes).encode())
    _store_bytes(h, worker.store.nodes)
    t.recording = True
    for _ in range(12):
        t.step(worker)
    h.update(repr(t.mm_shapes).encode())
    for a, b, out in t.mm_samples:
        for x in (a, b, out):
            h.update(np.ascontiguousarray(x).tobytes())
    h.update(repr(t.repaired).encode())
    _store_bytes(h, worker.store.nodes)
    assert h.hexdigest() == ONE_WORKER_DIGEST


@pytest.mark.parametrize("metric", [m["name"] for m in
                                    BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(cb_harness.reader(metric))


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_to_measure_on_a_cpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_run_refuses_without_the_system_under_test(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "system under test is missing" in p.stderr
    assert "{" not in p.stdout


#: n=4, k=2, d=3 at MSR with 40 rows a node, so a lost half of a node's
#: rows is more than ``subset_rank_deficit``'s limit
WIDE = dict(TINY, name="wide", cell_bytes=16, alpha=40, M=80)


def _repaired_store(change):
    """What ``store_numbers`` reads of one worker that repaired node 0,
    its store made by the program and then changed by ``change``."""
    import cb_gf
    from cb_program import Program
    store = Program(WIDE).store(
        cb_traffic.data_file(SEED, WIDE["M"], WIDE["cell_bytes"]), SEED,
        cb_gf.matmul)
    change(store.nodes)
    return argparse.Namespace(
        config=WIDE, workers=[argparse.Namespace(index=0, store=store)],
        repaired=[0], worker_of={"repaired": [0]})


def _repeat_a_row(nodes):
    for x in (nodes[0].vectors, nodes[0].payload):
        x[-1] = x[0]


def _zero_half_the_rows(nodes):
    for x in (nodes[0].vectors, nodes[0].payload):
        x[WIDE["alpha"] // 2:] = 0


@pytest.mark.parametrize("change,rows_short,correct", [
    (lambda nodes: None, (0, 1), True),
    (_repeat_a_row, (1, 2), True),
    (_zero_half_the_rows, (WIDE["alpha"] // 2, WIDE["alpha"]), False),
    (lambda nodes: nodes.pop(0), (WIDE["alpha"], WIDE["M"]), False)],
    ids=["sound", "a_row_repeated", "half_the_rows_lost", "node_missing"])
def test_subset_rank_deficit_reads_the_rows_a_subset_is_short(
        change, rows_short, correct):
    numbers, wrong = cb_check.store_numbers(_repaired_store(change), SEED)
    deficit = dict((name, value) for name, value, _ in numbers)[
        "subset_rank_deficit"]
    assert rows_short[0] <= deficit <= rows_short[1]
    assert cb_check.within(numbers) is correct
    assert (wrong > 0) is not correct
