"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` (or ``BENCH_QUICK=1``
in the environment) selects a fast smoke pass (fewer shapes / Monte-Carlo
batches of 80 instead of 120 trials), ``--seed`` (or ``BENCH_SEED``) the
root seed, ``--engine`` (or ``BENCH_ENGINE``) the planning engine the
fig6/7/8 drivers sweep with (default "batched", the golden-pinned
configuration; "jax" opts into the jit tier), and ``--modules`` restricts
the run to a subset (``planning`` is an alias for the fig6/7/8 trio CI
uses); every run also writes JSON artifacts under ``benchmarks/artifacts/``
(consumed by EXPERIMENTS.md).

Every run additionally consolidates the planning-relevant results into
``BENCH_planning.json`` at the repo root — per-figure-row ``us_per_call``
plus per-scheme mean planner wall time (``plan_ms``) aggregated from the
fig6/fig7/fig8 artifacts, and a ``plans`` section with the *deterministic*
per-point plan values (norm_time / norm_traffic / time_s; no timings) that
``benchmarks/golden/planning_quick_seed0.json`` pins bitwise in CI — so
both the perf trajectory and the planned values of the batched planning
engine (repro.core.batched) are machine-trackable across PRs.  Since
schema v2 the summary also carries a ``profile`` section (per-stage
planner wall times from ``repro.obs.PlannerProfile`` over a seeded
interior-alpha batch, per batched scheme) and a ``schema_version`` +
``meta`` header (seed, quick flag, git describe — resolved at import,
before any artifact writes can dirty the tree).  The summary additionally
carries an ``engine_jax`` A/B section: steady-state batched-vs-jax
per-plan wall time and plans-per-second for fr/ftr on the profile batch,
compile warm-up excluded (omitted with ``available: false`` when jax is
not importable).

Modules:
  fig6_d_sweep    — Fig. 6 (regeneration time & bandwidth vs d)
  fig7_bandwidth  — Fig. 7 (capacity-variance sweep)
  fig8_alpha      — Fig. 8 (MSR -> MBR storage sweep)
  fig10_rctree    — Fig. 10 (RCTREE MDS collapse, data-plane RLNC sim)
  kernel_gf       — GF(2^8) Pallas kernel cost model + timings
  ft_recovery     — beyond-paper: checkpoint-recovery planning on TPU fleet
  fleet_scale     — beyond-paper: event-driven fleet simulator sweep
  roofline        — reads the dry-run artifacts (launch/dryrun.py) if present

One root seed (``BENCH_SEED``, default 0) is threaded into every module
whose ``run`` accepts ``root_seed`` — the fleet sweep derives all of its
scenario seeds from it, which is what makes ``BENCH_fleet.json`` bitwise
reproducible across runs on the same machine.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import traceback

MODULES = [
    "fig6_d_sweep",
    "fig7_bandwidth",
    "fig8_alpha",
    "fig10_rctree",
    "kernel_gf",
    "ft_recovery",
    "fleet_scale",
    "roofline",
]

PLANNING_MODULES = ("fig6_d_sweep", "fig7_bandwidth", "fig8_alpha")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scheme_plan_ms(ran_modules) -> dict:
    """Mean per-scheme planner wall time over the fig6/7/8 artifacts THIS
    run produced (stale artifact files from earlier runs are ignored so the
    summary never mixes trial counts or quick/full settings)."""
    from .common import ARTIFACT_DIR

    acc: dict = {}
    for mod in PLANNING_MODULES:
        if mod not in ran_modules:
            continue
        path = os.path.join(ARTIFACT_DIR, f"{mod}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            data = json.load(f)
        for point in data.get("points", []):
            for scheme, vals in point.items():
                if isinstance(vals, dict) and "plan_ms" in vals:
                    acc.setdefault(scheme, []).append(vals["plan_ms"])
    return {s: sum(v) / len(v) for s, v in acc.items() if v}


def _plan_values(ran_modules) -> dict:
    """Deterministic per-point plan values from THIS run's fig6/7/8
    artifacts: everything except the wall-time fields.  These are pure
    functions of (seed, quick) — the exact witness oracle has no solver
    noise — so CI pins them bitwise (benchmarks/golden/)."""
    from .common import ARTIFACT_DIR

    out: dict = {}
    for mod in PLANNING_MODULES:
        if mod not in ran_modules:
            continue
        path = os.path.join(ARTIFACT_DIR, f"{mod}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            data = json.load(f)
        pts = []
        for point in data.get("points", []):
            pt = {}
            for key, vals in point.items():
                if isinstance(vals, dict):
                    pt[key] = {m: v for m, v in vals.items() if m != "plan_ms"}
                else:
                    pt[key] = vals
            pts.append(pt)
        out[mod] = pts
    return out


def _registry_info() -> dict:
    """The scheme family as the registry declares it — recorded in the
    summary so a BENCH_planning.json is self-describing about which schemes
    the (registry-enumerated, not hardcoded) fig drivers swept."""
    from repro.core import scheme_names

    return {"schemes": list(scheme_names()),
            "batched": list(scheme_names(batched=True)),
            "jax": list(scheme_names(jax=True))}


def _profile_section(quick: bool, seed: int) -> dict:
    """Per-stage planner profile (ISSUE 7): run every batched scheme once
    over a seeded interior-alpha batch with a ``repro.obs.PlannerProfile``
    attached, and record stage wall times / counters.  The interior alpha
    (halfway MSR -> MBR) is deliberate: it exercises fr's star_bisection +
    witness stages and ftr's full candidate/local-search pipeline, which
    the pure-MSR closed form would skip.  Wall times are machine noise by
    nature; the golden guard only pins ``plans``, never this section."""
    import numpy as np

    from repro.core import CodeParams, mbr_point, plan_many, scheme_names
    from repro.obs import PlannerProfile

    B = 64 if quick else 256
    M, k, d, n = 600.0, 3, 6, 12
    a_msr = M / k
    a_mbr, _ = mbr_point(M, k, d)
    params = CodeParams(n=n, k=k, d=d, M=M, alpha=0.5 * (a_msr + a_mbr))
    rng = np.random.default_rng([seed, 0x0B5])
    caps = rng.uniform(10.0, 120.0, size=(B, d + 1, d + 1))
    idx = np.arange(d + 1)
    caps[:, idx, idx] = 0.0
    out = {}
    for scheme in scheme_names(batched=True):
        prof = PlannerProfile()
        plan_many(caps, params, scheme, engine="batched", profile=prof)
        out[scheme] = prof.summary()
    return out


def _engine_jax_section(quick: bool, seed: int) -> dict:
    """A/B wall time of the NumPy batched engine vs the jit-compiled jax
    tier on the same seeded interior-alpha batch the ``profile`` section
    uses (fr's star bisection + witness, ftr's full candidate/local-search
    pipeline).  Jit compilation is warmed up outside the timed region —
    the numbers are steady-state per-plan costs, min-of-3.

    Honesty note: these are *measurements*, not marketing.  On a 1-core
    CPU container XLA's per-row cost exceeds NumPy's SIMD row cost and the
    lockstep jit program cannot compact converged lanes the way the NumPy
    engine does, so the jax tier is typically SLOWER here for ftr; its
    value on this hardware is parity-guarded accelerator readiness (see
    repro.core.jax_engine).  Wall times are machine noise by nature; the
    golden guard never pins this section.  Omitted when jax is absent.
    """
    import time

    import numpy as np

    from repro.core import CodeParams, mbr_point, plan_many, scheme_names

    jax_capable = scheme_names(jax=True)
    if not jax_capable:
        return {"available": False,
                "note": "jax not importable in this environment"}
    B = 64 if quick else 256
    M, k, d, n = 600.0, 3, 6, 12
    a_mbr, _ = mbr_point(M, k, d)
    params = CodeParams(n=n, k=k, d=d, M=M, alpha=0.5 * (M / k + a_mbr))
    rng = np.random.default_rng([seed, 0x0B5])
    caps = rng.uniform(10.0, 120.0, size=(B, d + 1, d + 1))
    idx = np.arange(d + 1)
    caps[:, idx, idx] = 0.0

    def best_of(fn, reps=3):
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    section = {"available": True, "batch": B, "d": d,
               "cpu_count": os.cpu_count(), "schemes": {}}
    for scheme in ("fr", "ftr"):
        plan_many(caps, params, scheme, engine="jax")      # compile warm-up
        t_np = best_of(lambda: plan_many(caps, params, scheme,
                                         engine="batched"))
        t_jx = best_of(lambda: plan_many(caps, params, scheme,
                                         engine="jax"))
        section["schemes"][scheme] = {
            "batched_plan_ms": round(t_np / B * 1e3, 4),
            "jax_plan_ms": round(t_jx / B * 1e3, 4),
            "batched_plans_per_s": round(B / t_np, 1),
            "jax_plans_per_s": round(B / t_jx, 1),
            "jax_speedup": round(t_np / t_jx, 3),
        }
    return section


def _write_planning_summary(rows_by_module: dict) -> None:
    from .common import BENCH_SCHEMA_VERSION, bench_engine, run_meta

    quick = os.environ.get("BENCH_QUICK", "0") == "1"
    seed = int(os.environ.get("BENCH_SEED", "0"))
    summary = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "meta": run_meta(seed, engine=bench_engine()),
        "quick": quick,
        "seed": seed,
        "registry": _registry_info(),
        "rows": {
            r["name"]: round(r["us_per_call"], 3)
            for mod in PLANNING_MODULES
            for r in rows_by_module.get(mod, [])
        },
        "schemes": {s: {"plan_ms": round(ms, 4)}
                    for s, ms in _scheme_plan_ms(rows_by_module).items()},
        "plans": _plan_values(rows_by_module),
        "profile": _profile_section(quick, seed),
        "engine_jax": _engine_jax_section(quick, seed),
    }
    path = os.path.join(REPO_ROOT, "BENCH_planning.json")
    with open(path, "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True, allow_nan=False)


def _parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        description="run the benchmark modules (CSV to stdout, JSON "
                    "artifacts under benchmarks/artifacts/)")
    ap.add_argument("--quick", action="store_true",
                    help="fast smoke pass (same as BENCH_QUICK=1)")
    ap.add_argument("--seed", type=int, default=None,
                    help="root seed (same as BENCH_SEED; default 0)")
    ap.add_argument("--engine", default=None,
                    choices=("batched", "scalar", "jax"),
                    help="planning engine for the fig6/7/8 drivers (same "
                         "as BENCH_ENGINE; default batched — the "
                         "golden-pinned configuration)")
    ap.add_argument("--modules", nargs="+", default=None, metavar="MOD",
                    help="subset of modules to run; 'planning' expands to "
                         f"{'/'.join(PLANNING_MODULES)}")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = _parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # the flags are sugar over the env vars every module already reads
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"
    if args.seed is not None:
        os.environ["BENCH_SEED"] = str(args.seed)
    if args.engine is not None:
        os.environ["BENCH_ENGINE"] = args.engine
    modules = MODULES
    if args.modules is not None:
        modules = []
        for m in args.modules:
            modules.extend(PLANNING_MODULES if m == "planning" else [m])
        unknown = [m for m in modules if m not in MODULES]
        if unknown:
            raise SystemExit(f"unknown benchmark modules {unknown}; "
                             f"available: {MODULES}")
    print("name,us_per_call,derived")
    root_seed = int(os.environ.get("BENCH_SEED", "0"))
    failures = []
    rows_by_module: dict = {}
    for mod_name in modules:
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
        except ModuleNotFoundError as e:
            if f"benchmarks.{mod_name}" in str(e):
                continue  # optional module not built yet
            raise
        try:
            kwargs = ({"root_seed": root_seed}
                      if "root_seed" in inspect.signature(mod.run).parameters
                      else {})
            rows = list(mod.run(**kwargs))
            rows_by_module[mod_name] = rows
            for r in rows:
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
            sys.stdout.flush()
        except Exception:
            failures.append(mod_name)
            traceback.print_exc()
    if any(m in rows_by_module for m in PLANNING_MODULES):
        try:
            _write_planning_summary(rows_by_module)
        except Exception:
            failures.append("BENCH_planning.json")
            traceback.print_exc()
    else:
        # a --modules run without any fig6/7/8 module must not clobber the
        # tracked BENCH_planning.json with an empty summary
        print("note: no planning module ran; BENCH_planning.json untouched",
              file=sys.stderr)
    if failures:
        raise SystemExit(f"benchmark modules failed: {failures}")


if __name__ == "__main__":
    main()
