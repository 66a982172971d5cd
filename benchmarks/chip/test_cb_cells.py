"""Rehearsals of every traffic mix on the CPU at a tiny configuration, with
the interpreted kernel: the output check passes on the program and fails on
the control and on every planted fault; the command refuses to measure
without a TPU or without the system under test."""
import argparse
import io
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import cb_control
import cb_harness

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


def rehearse(cell_name, program=None, seconds=0.3, seed=2**31 + 77):
    import jax
    bench, cell, _, mix = cb_harness.load_cell(cell_name)
    mix = dict(mix, batch=[min(b, 8) for b in mix["batch"]])
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
