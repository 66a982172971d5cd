"""Fleet-scale sweep: cluster size x failure rate x repair policy.

Runs the event-driven fleet simulator (``repro.fleet``) over the scenario
library and writes two artifacts:

* ``benchmarks/artifacts/fleet_scale.json`` — the usual per-module record;
* ``BENCH_fleet.json`` at the repo root — the machine-trackable fleet
  metrics (backlog, p50/p99 regeneration time under contention,
  vulnerability window, MTTDL estimate) per configuration.

Determinism: every configuration's simulator seed is derived from one root
seed (threaded in by ``benchmarks/run.py``, or ``--seed`` on the CLI) and
the config name via crc32, and no wall-clock measurement enters the JSON —
``BENCH_fleet.json`` is bitwise reproducible across runs on one machine.
Wall time only feeds the ``us_per_call`` CSV column.

The sweep includes a repair-lifecycle column (PR 3): the abort-heavy
``flaky_providers`` scenario per policy with partial-progress carryover and
in-flight plan migration off (``..._<pol>``, the default path), carryover
only (``..._carry``), and carryover + migration (``..._mig``).  Rows whose
name carries no lifecycle suffix run the pre-PR-3 dynamics bitwise;
``benchmarks/golden/fleet_quick_seed0.json`` pins their quick-mode values
and CI fails on any diff (see tests/test_fleet.py, ci.yml, and
``benchmarks/check_fleet_golden.py``).

A plan-vs-reality robustness column (ISSUE 6) runs the ``stragglers``
(silent link brownouts) and ``foggy_estimates`` (stale/noisy capacity
estimates) scenarios with mitigation off and on (``..._robust``:
watchdog + retry/backoff + degraded-d admission); each summary carries the
plan-error distribution (realized vs predicted (re)plan ETA).

Observability (ISSUE 7): ``--trace`` re-runs every configuration with the
flight recorder on, asserts the traced summary is bitwise identical to the
untraced one (tracing is observation, never perturbation), and writes one
``<name>.jsonl`` event log plus one ``<name>.trace.json`` Chrome/Perfetto
trace per config under ``benchmarks/artifacts/traces/``.  Both JSON roots
are strict JSON since schema v2 — non-finite floats (the quiet scenarios'
``mttdl_estimate``) serialize as ``null``, never the invalid ``Infinity``
literal — and carry a ``schema_version`` + ``meta`` header (root seed,
quick flag, git describe).

Region scale (ISSUE 8): two additions ride on the incremental sharing
engine.  ``ensemble_*`` config rows run K independent clusters (distinct
seed streams, one scenario) through the lockstep multi-cluster driver
(``repro.fleet.ensemble``) and report the pooled region-level summary
plus cluster-bootstrap confidence intervals under a ``cis`` key; K is
overridable with ``--clusters``.  A separate ``perf`` section measures
event-loop throughput (wall-clock us per event) on three fixed rows and
records the frozen PR-7 (full-rescan engine) reference alongside — the
speedup the incremental engine is accountable for.  The ``configs``
section stays bitwise reproducible; ``perf`` is wall clock by design and
is guarded by ``benchmarks/check_fleet_perf.py`` (machine-normalized,
like the planning tripwire), never by the bitwise golden.

Coded data plane (ISSUE 10): ``dataplane_*`` config rows run the fleet
with real payloads — degraded reads as k-fragment transfers, repairs
producing RLNC-coded blocks through ``repro.coding.rlnc`` with decode
verification — and carry the read-latency percentiles, wire-byte
counters, and a ``dataplane_links`` top-10 (per-link repair/read bytes)
next to the usual summary.  One row replays an open-loop arrival trace
generated to ``benchmarks/artifacts/read_workload.jsonl``.

CLI: ``python -m benchmarks.fleet_scale [--quick] [--seed N] [--trace]
[--clusters K]`` (CI runs the ``--quick`` smoke, which asserts the
artifact exists and backlog is finite, plus a ``--trace`` pass checked
by check_trace.py).
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
import zlib

from repro.compile_cache import enable_compile_cache
from repro.core import CodeParams
from repro.fleet import SCENARIOS, ClusterEnsemble, FleetSimulator, \
    ReadTrace, Scenario, generate_trace, make_policy, mitigated, simulate
from repro.fleet.scenario import uniform_matrix
from repro.obs import json_sanitize

from .common import BENCH_SCHEMA_VERSION, quick_mode, row, run_meta, \
    save_artifact

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "artifacts", "traces")

# ~events per simulation: duration is sized as EVENT_BUDGET failures in
# expectation, so sweeping the failure rate changes contention, not cost
EVENT_BUDGET_QUICK = 40
EVENT_BUDGET = 150


def _config_seed(root_seed: int, name: str) -> int:
    return (root_seed * 1_000_003 + zlib.crc32(name.encode())) % (1 << 31)


# -- event-loop throughput rows (ISSUE 8) -----------------------------------
# Fixed scenarios sized so the event loop, not the planner, is the cost:
# heavy degraded-read traffic (eventloop), abort churn (churn), and a
# moderate read mix (readmix).  ``PR7_US_PER_EVENT`` freezes the
# pre-incremental-engine (full-rescan) measurement of the SAME rows on the
# reference machine — best of 5, identical event sequences (the engines
# agree bitwise on every metric) — so ``speedup_vs_pr7`` in the perf
# section is an apples-to-apples event-loop ratio, not a machine artifact.
PERF_REPEATS = 3


def _perf_rows():
    cap = uniform_matrix(0.3, 8.0)
    yield "eventloop_n96_star", Scenario(
        num_nodes=96, duration=2500.0, failure_rate=6e-3,
        capacity_model=cap, max_concurrent=64,
        read_rate=8.0, read_duration=60.0), "star"
    yield "churn_n64_star", Scenario(
        num_nodes=64, duration=1500.0, failure_rate=1.2e-2,
        capacity_model=cap, max_concurrent=32), "star"
    yield "readmix_n96_star", Scenario(
        num_nodes=96, duration=2000.0, failure_rate=4e-3,
        capacity_model=cap, max_concurrent=48,
        read_rate=2.0, read_duration=30.0), "star"


PR7_US_PER_EVENT = {
    "eventloop_n96_star": 231.2,
    "churn_n64_star": 719.6,
    "readmix_n96_star": 180.4,
}


def _ensemble_rows(quick: bool, clusters: int = 0):
    """(name, scenario, policy, K) rows for the lockstep multi-cluster
    driver.  ``clusters`` overrides the per-row default K when > 0."""
    cap = uniform_matrix(0.3, 8.0)
    if quick:
        rows = [("ensemble_n96", Scenario(
            num_nodes=96, duration=150.0, failure_rate=4e-3,
            capacity_model=cap, max_concurrent=16), "star", 2)]
    else:
        rows = [
            ("ensemble_n96", Scenario(
                num_nodes=96, duration=600.0, failure_rate=4e-3,
                capacity_model=cap, max_concurrent=32), "star", 4),
            ("ensemble_n256", Scenario(
                num_nodes=256, duration=300.0, failure_rate=2e-3,
                capacity_model=cap, max_concurrent=48), "star", 4),
        ]
    for name, sc, pol, k in rows:
        k = clusters if clusters > 0 else k
        yield f"{name}_K{k}_{pol}", sc, pol, k


ENSEMBLE_CI_KEYS = ("mean_backlog", "regen_p50", "regen_p99",
                    "vulnerability_p99", "unavail_fraction",
                    "mttdl_estimate")


def _params(d: int = 6) -> CodeParams:
    return CodeParams.msr(n=12, k=3, d=d, M=600.0)


def _sweep(quick: bool):
    """Yield (name, scenario, policy_spec) configurations."""
    sizes = (16,) if quick else (16, 32, 64)
    rates = (2e-3,) if quick else (1e-3, 4e-3)
    policies = (("star", "ftr", "flexible") if quick
                else ("star", "fr", "tr", "ftr", "flexible"))
    budget = EVENT_BUDGET_QUICK if quick else EVENT_BUDGET
    for n in sizes:
        for lam in rates:
            duration = budget / (lam * n)
            for pol in policies:
                sc = SCENARIOS["steady"](n, failure_rate=lam,
                                         duration=duration)
                yield f"n{n}_lam{lam:g}_{pol}", sc, pol
    if not quick:
        # scenario-library column at fixed size/rate for the two best
        # policies: rack bursts, capacity weather, degraded reads, tiered
        n, lam = 24, 2e-3
        duration = budget / (lam * n)
        for kind in ("rack_bursts", "capacity_weather", "hot_reads",
                     "tiered"):
            for pol in ("ftr", "flexible"):
                sc = SCENARIOS[kind](n, failure_rate=lam, duration=duration)
                yield f"{kind}_n{n}_{pol}", sc, pol
    # repair-lifecycle column (policy x migration): the abort-heavy
    # flaky_providers scenario, per policy with the lifecycle machinery
    # off (default path — bitwise-guarded), carryover only, and
    # carryover + in-flight migration
    n, lam = 16, 4e-3
    duration = budget / (lam * n)
    for pol in ("flexible",) if quick else ("ftr", "flexible"):
        sc = SCENARIOS["flaky_providers"](n, failure_rate=lam,
                                          duration=duration)
        yield f"flaky_providers_n{n}_{pol}", sc, pol
        yield (f"flaky_providers_n{n}_{pol}_carry",
               dataclasses.replace(sc, carryover=True), pol)
        yield (f"flaky_providers_n{n}_{pol}_mig",
               dataclasses.replace(sc, carryover=True, migration=True), pol)
        # bank-aware migration (ISSUE 8): the simulator scores every
        # candidate scheme's replan by credited residual ETA instead of
        # taking the policy's nominal-time pick
        yield (f"flaky_providers_n{n}_{pol}_bankmig",
               dataclasses.replace(sc, carryover=True, migration=True,
                                   bank_aware_migration=True), pol)
    # plan-vs-reality robustness column (ISSUE 6): silent brownouts
    # (stragglers) and stale/noisy capacity estimates (foggy_estimates),
    # each with mitigation off (the injections alone) and on
    # (``..._robust``: watchdog + retry/backoff + degraded-d).  The
    # plan-error percentiles quantify how far predictions drift from
    # reality; the robust rows show what the watchdog buys back.
    n, lam = 16, 2e-3
    duration = budget / (lam * n)
    for kind in ("stragglers", "foggy_estimates"):
        sc = SCENARIOS[kind](n, failure_rate=lam, duration=duration)
        yield f"{kind}_n{n}_flexible", sc, "flexible"
        yield f"{kind}_n{n}_flexible_robust", mitigated(sc), "flexible"


def _dataplane_rows(quick: bool, root_seed: int):
    """(name, scenario, policy) rows exercising the coded data plane
    (ISSUE 10): reads and repairs as real fragment/block transfers.

    * ``..._storm_...`` — the hot_reads scenario under a capacity storm
      (fast, deep shocks) with decode verification on: every completed
      repair's regenerated blocks must keep the mini code store
      k-of-n decodable.
    * ``..._trace_...`` — the same data plane driven by an open-loop
      arrival trace generated to a JSONL file and replayed (the
      millions-of-arrivals path, exercised here at bench scale).  The
      workload file lands in ``benchmarks/artifacts/`` — NOT under
      ``traces/``, which check_trace.py globs for flight-recorder logs.
    """
    n, lam = 16, 2e-3
    budget = EVENT_BUDGET_QUICK if quick else EVENT_BUDGET
    duration = budget / (lam * n)
    storm = dataclasses.replace(
        SCENARIOS["hot_reads"](n, failure_rate=lam, duration=duration,
                               dataplane=True, dataplane_verify=True),
        shock_period=duration / 8, shock_lo=0.35)
    yield f"dataplane_hot_reads_storm_n{n}_flexible", storm, "flexible"
    art_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "artifacts")
    os.makedirs(art_dir, exist_ok=True)
    workload = os.path.join(art_dir, "read_workload.jsonl")
    generate_trace(workload, rate=0.1, duration=duration,
                   seed=_config_seed(root_seed, "read_workload"))
    replay = SCENARIOS["hot_reads"](
        n, failure_rate=lam, duration=duration, dataplane=True,
        read_trace=ReadTrace(path=workload), dataplane_verify=True)
    yield f"dataplane_hot_reads_trace_n{n}_ftr", replay, "ftr"


def _trace_config(name: str, sc, pol: str, params, seed: int,
                  untraced_summary: dict, root_seed: int) -> None:
    """Re-run one configuration with the flight recorder on, assert the
    traced summary equals the untraced one bitwise (tracing must never
    perturb the simulation), and write the JSONL + Chrome trace files."""
    sim = FleetSimulator(dataclasses.replace(sc, trace=True),
                         make_policy(pol), params, seed=seed)
    traced = sim.run().summary()
    assert traced == untraced_summary, \
        f"{name}: traced summary diverged from untraced (tracing perturbed " \
        f"the simulation)"
    sim.recorder.meta.update(config=name, root_seed=root_seed, seed=seed)
    os.makedirs(TRACE_DIR, exist_ok=True)
    sim.recorder.save_jsonl(os.path.join(TRACE_DIR, f"{name}.jsonl"))
    sim.recorder.save_chrome(os.path.join(TRACE_DIR,
                                          f"{name}.trace.json"))


def run(root_seed: int = 0, trace: bool = False, clusters: int = 0):
    quick = quick_mode()
    params = _params()
    rows, configs = [], {}
    for name, sc, pol in _sweep(quick):
        seed = _config_seed(root_seed, name)
        t0 = time.perf_counter()
        summary = simulate(sc, make_policy(pol), params, seed=seed)
        wall = time.perf_counter() - t0
        assert math.isfinite(summary["mean_backlog"]), name
        assert summary["regen_p50"] >= 0 and summary["regen_p99"] >= 0, name
        if trace:
            _trace_config(name, sc, pol, params, seed, summary, root_seed)
        configs[name] = summary
        events = max(summary["completed"] + summary["aborted"], 1)
        rows.append(row(
            f"fleet/{name}", wall / events * 1e6,
            f"backlog={summary['mean_backlog']:.3f} "
            f"p99={summary['regen_p99']:.3f}s "
            f"vuln_p99={summary['vulnerability_p99']:.3f}s "
            f"mig={summary['migrations']:.0f} "
            f"saved={summary['work_saved_fraction']:.2f} "
            f"plan_err={summary['plan_err_mean']:.2f}"))
    # coded data plane rows (ISSUE 10): run the simulator directly so the
    # per-link wire-byte ledger can ride in the artifact next to the
    # summary (``dataplane_links``); ``simulate()`` would discard it
    for name, sc, pol in _dataplane_rows(quick, root_seed):
        seed = _config_seed(root_seed, name)
        t0 = time.perf_counter()
        sim = FleetSimulator(sc, make_policy(pol), params, seed=seed)
        summary = sim.run().summary()
        wall = time.perf_counter() - t0
        assert math.isfinite(summary["mean_backlog"]), name
        assert summary["reads_completed"] > 0, name
        assert summary["decode_failures"] == 0, name
        assert summary["repair_bytes"] > 0 and summary["read_bytes"] > 0, name
        if trace:
            _trace_config(name, sc, pol, params, seed, summary, root_seed)
        configs[name] = dict(summary,
                             dataplane_links=sim.dataplane.top_links(10))
        events = max(summary["completed"] + summary["aborted"], 1)
        rows.append(row(
            f"fleet/{name}", wall / events * 1e6,
            f"reads={summary['reads_completed']} "
            f"read_p99={summary['read_p99']:.3f}s "
            f"repair_GB={summary['repair_bytes'] / 1e9:.1f} "
            f"read_GB={summary['read_bytes'] / 1e9:.1f} "
            f"decode_fail={summary['decode_failures']}"))
    # region-scale ensemble rows: K clusters in lockstep, pooled summary
    # plus cluster-bootstrap CIs.  Deterministic like every config row —
    # the bootstrap rng is seeded from the config seed.
    for name, sc, pol, k in _ensemble_rows(quick, clusters):
        seed = _config_seed(root_seed, name)
        t0 = time.perf_counter()
        ens = ClusterEnsemble(sc, lambda p=pol: make_policy(p), params,
                              clusters=k, root_seed=seed)
        ens.run()
        wall = time.perf_counter() - t0
        summary = ens.pooled().summary()
        assert math.isfinite(summary["mean_backlog"]), name
        cis = ens.cis(ENSEMBLE_CI_KEYS, n_boot=200, seed=seed)
        configs[name] = dict(summary, clusters=k,
                             cis={key: list(v) for key, v in cis.items()})
        events = max(summary["completed"] + summary["aborted"], 1)
        lo, mid, hi = cis["mean_backlog"]
        rows.append(row(
            f"fleet/{name}", wall / events * 1e6,
            f"K={k} backlog={mid:.3f} [{lo:.3f},{hi:.3f}] "
            f"p99={summary['regen_p99']:.3f}s "
            f"vuln_p99={summary['vulnerability_p99']:.3f}s"))
    # event-loop throughput section: wall clock by design, so it lives
    # OUTSIDE ``configs`` (that section stays bitwise reproducible) and is
    # guarded by check_fleet_perf.py instead of the golden
    perf = {}
    for name, sc, pol in _perf_rows():
        seed = _config_seed(root_seed, name)
        best = None
        for _ in range(PERF_REPEATS):
            sim = FleetSimulator(sc, make_policy(pol), params, seed=seed)
            t0 = time.perf_counter()
            sim.run()
            wall = time.perf_counter() - t0
            if best is None or wall < best[0]:
                best = (wall, sim.loop_events)
        wall, events = best
        us = wall / events * 1e6
        pr7 = PR7_US_PER_EVENT[name]
        perf[name] = {
            "us_per_event": us,
            "loop_events": events,
            "pr7_us_per_event": pr7,
            "speedup_vs_pr7": pr7 / us,
        }
        rows.append(row(f"fleet_perf/{name}", us,
                        f"events={events} pr7={pr7:.1f}us/ev "
                        f"speedup={pr7 / us:.2f}x"))
    artifact = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "meta": run_meta(root_seed, sweep="quick" if quick else "full"),
        "quick": quick,
        "root_seed": root_seed,
        "configs": configs,
        "perf": perf,
    }
    # strict JSON: `Infinity` is not JSON — sanitize non-finite floats
    # (quiet scenarios' mttdl_estimate) to null and forbid the literal
    artifact = json_sanitize(artifact)
    save_artifact("fleet_scale", artifact)
    with open(os.path.join(REPO_ROOT, "BENCH_fleet.json"), "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True, allow_nan=False)
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small sweep (CI smoke)")
    ap.add_argument("--seed", type=int, default=0, help="root seed")
    ap.add_argument("--trace", action="store_true",
                    help="also re-run each config with the flight recorder "
                         "on and write benchmarks/artifacts/traces/")
    ap.add_argument("--clusters", type=int, default=0,
                    help="override K for the ensemble rows (0 = per-row "
                         "defaults)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"
    print("name,us_per_call,derived")
    for r in run(root_seed=args.seed, trace=args.trace,
                 clusters=args.clusters):
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    path = os.path.join(REPO_ROOT, "BENCH_fleet.json")
    assert os.path.exists(path), "BENCH_fleet.json was not written"

    def _reject(const):  # strict JSON: Infinity/NaN literals are a bug
        raise ValueError(f"non-strict JSON literal {const} in {path}")

    with open(path) as f:
        data = json.load(f, parse_constant=_reject)
    assert data["schema_version"] == BENCH_SCHEMA_VERSION, "stale schema"
    assert all(math.isfinite(c["mean_backlog"])
               for c in data["configs"].values()), "non-finite backlog"
    print(f"# wrote {path} ({len(data['configs'])} configs)")
    if args.trace:
        n_traces = len([p for p in os.listdir(TRACE_DIR)
                        if p.endswith(".jsonl")])
        print(f"# wrote {n_traces} traces under {TRACE_DIR}")


if __name__ == "__main__":
    main()
