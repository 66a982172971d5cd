#!/usr/bin/env python3
"""Smoke test of the repair path on one TPU, through its normal entry points.

    python chip_smoke.py [--seed N]

The deployment is the HDFS erasure-coding policy RS-6-3-1024k (Apache
Hadoop 3 documentation, "HDFS Erasure Coding"): n=9 nodes, k=6, repairs
from d=8 helpers at the MSR point, 1 MiB striping cells.  All data comes
from ``--seed``.  Phases, in order; each must pass before the next starts:

1. device: JAX's first device must be a TPU.  There is no CPU path.
2. planner: ``plan_many(..., engine="jax")`` plans FR and FTR on seeded
   heterogeneous overlays at B = 1, 2, 4 (the fleet's per-epoch admission
   batch) and 256 (a Monte-Carlo batch).  Parents must equal the NumPy
   batched engine's; times, betas, traffic and lower bounds must agree
   within 1e-9 (|a - b| <= 1e-9 * (1 + |b|)); every plan must validate
   against its overlay.
3. gf_kernel: every GF(2^8) matmul that one repair issues on 1 MiB cells
   goes through ``kernels.ops.gf_matmul``; each result must stay on the TPU
   and be byte-equal to the table-based ``GF8.matmul``.
4. fleet: a 9-node ``FleetSimulator`` run with the coded data plane on the
   kernel, three injected failures and open-loop reads, driven by the jax
   FTR policy.  Every failure must be repaired and decode-checked, reads
   must complete (some while a node is down), and k nodes including
   repaired ones must reconstruct the file byte for byte.  The same seed
   then runs with the batched planner on the kernel and on NumPy tables:
   the three coded stores must be byte-equal.

Earlier lines report each phase; the last line of standard output is one
JSON object, ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": ...}}``.  A failed check exits non-zero before that line.  Times
are host wall clock around work that ends on the host; this is a bring-up
check, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
import warnings
from unittest import mock

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
N, K, D = 9, 6, 8
CELL = 1 << 20                    # one HDFS striping cell, bytes
BATCHES = (1, 2, 4, 256)
SCHEMES = ("fr", "ftr")
REL_TOL = 1e-9
# one node down at a time, so every repair finds d=8 live helpers; a
# repair lasts tens of milliseconds here, so the read rate is high enough
# that several reads arrive while a node is down
FAILURES = ((1.0, 0), (4.0, 3), (7.0, 7))
READ_RATE = 100.0                 # open-loop reads per simulated second
DURATION = 10.0


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


class CompileClock:
    """Seconds JAX spends compiling (or loading a compiled program from the
    persistent cache), read from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.seconds, self.cache_hits = 0.0, 0


def code_params():
    from repro.core import CodeParams
    # M = 2k blocks, the fleet data plane's coded-store size, so the store
    # and the cluster code share one block unit (alpha = 2 cells per node)
    return CodeParams.msr(n=N, k=K, d=D, M=2.0 * K)


def overlays(seed: int, count: int):
    """Seeded heterogeneous overlays from the paper's U[10, 120] sampler."""
    from repro.core import caps_tensor
    from repro.storage.capacities import uniform
    rng = random.Random(seed)
    sample = uniform()
    nets = [sample(rng, D) for _ in range(count)]
    return nets, caps_tensor(nets)


def _worst_dev(a, b) -> float:
    a = np.asarray(a, dtype=float)
    b = np.asarray(b, dtype=float)
    fin = np.isfinite(b)
    if not np.array_equal(fin, np.isfinite(a)) or \
            not np.array_equal(a[~fin], b[~fin]):
        return float("inf")
    if not fin.any():
        return 0.0
    return float((np.abs(a[fin] - b[fin]) / (1.0 + np.abs(b[fin]))).max())


def phase_planner(seed: int, clock: CompileClock, batches=BATCHES):
    """jax vs batched planner parity; returns the B=1 FTR plan of overlay 0."""
    from repro.core import plan_many, plans_from_batch

    params = code_params()
    nets, caps = overlays(seed, max(batches))
    first = None
    for scheme in SCHEMES:
        for B in batches:
            clock.reset()
            t0 = time.perf_counter()
            rj = plan_many(caps[:B], params, scheme, engine="jax")
            t_first = time.perf_counter() - t0
            compile_s, hits = clock.seconds, clock.cache_hits
            t0 = time.perf_counter()
            again = plan_many(caps[:B], params, scheme, engine="jax")
            t_warm = time.perf_counter() - t0
            rb = plan_many(caps[:B], params, scheme, engine="batched")
            require(rj.engine == "jax", f"{scheme} B={B} planned by "
                    f"{rj.engine!r}, not jax")
            require(np.array_equal(rj.parents, rb.parents),
                    f"{scheme} B={B}: parents differ from the batched engine")
            devs = {"times": _worst_dev(rj.times, rb.times),
                    "traffic": _worst_dev(rj.traffic, rb.traffic),
                    "betas": _worst_dev(rj.betas, rb.betas)}
            if rb.lower_bounds is not None:
                devs["lower_bounds"] = _worst_dev(rj.lower_bounds,
                                                  rb.lower_bounds)
            worst = max(devs.values())
            require(worst <= REL_TOL, f"{scheme} B={B}: jax vs batched "
                    f"deviation {devs} exceeds {REL_TOL}")
            require(np.array_equal(again.times, rj.times)
                    and np.array_equal(again.parents, rj.parents),
                    f"{scheme} B={B}: a second jax call planned differently")
            plans = plans_from_batch(rj, params)
            for plan, net in zip(plans, nets[:B]):
                plan.validate(net)
            require(all(np.isfinite(p.time) for p in plans),
                    f"{scheme} B={B}: a plan has no finite time")
            if scheme == "ftr" and B == 1:
                first = plans[0]
            say("planner", scheme=scheme, B=B, first_call_s=f"{t_first:.3f}",
                compile_s=f"{compile_s:.3f}", cache_hits=hits,
                warm_s=f"{t_warm:.4f}", worst_rel_dev=f"{worst:.3e}",
                parents="equal", valid=len(plans))
    return first


def phase_gf_kernel(seed: int, plan, cell: int = CELL) -> None:
    """Replay one repair's matmuls through the kernel on the device."""
    import jax
    from repro.coding.gf import GF8
    from repro.kernels.ops import gf_matmul
    from repro.storage.simulator import RlncSimulator

    calls = []

    def record(a, b):
        out = GF8.matmul(a, b)
        calls.append((np.asarray(a, np.uint8), np.asarray(b, np.uint8), out))
        return out

    store = RlncSimulator(code_params(), block_bytes=cell, seed=seed,
                          matmul=record)
    calls.clear()                        # keep the repair's, not the encode's
    store.execute_plan(plan, failed=0, provider_ids=list(range(1, D + 1)))
    require(len(calls) > 0, "the repair issued no GF matmul")
    backend = jax.devices()[0].platform
    seen = {}
    t_all = time.perf_counter()
    for a, b, want in calls:
        t0 = time.perf_counter()
        out = gf_matmul(a, b)
        out.block_until_ready()
        dt = time.perf_counter() - t0
        shape = (a.shape[0], a.shape[1], b.shape[1])
        seen.setdefault(shape, []).append(dt)
        require({d.platform for d in out.devices()} == {backend},
                f"gf_matmul{shape} result is not on the {backend}")
        require(np.array_equal(np.asarray(out), want),
                f"gf_matmul{shape} differs from GF8.matmul")
    total = time.perf_counter() - t_all
    payload = sorted(s for s in seen if s[2] == cell)
    say("gf_kernel", calls=len(calls), shapes=len(seen),
        payload_shapes="".join(f"({m}x{k}x{n})" for m, k, n in payload),
        first_call_s_max=f"{max(v[0] for v in seen.values()):.3f}",
        total_s=f"{total:.3f}", byte_equal="yes", on=backend)


def _fleet_run(seed: int, engine: str, matmul: str, cell: int):
    from repro.fleet import FleetSimulator, ReadTrace, Scenario, make_policy
    from repro.kernels import ops

    sc = Scenario(num_nodes=N, duration=DURATION, failures=FAILURES,
                  dataplane=True, dataplane_matmul=matmul,
                  dataplane_verify=True, dataplane_payload_bytes=cell,
                  dataplane_block_bytes=float(cell),
                  read_trace=ReadTrace(rate=READ_RATE), trace=True)
    with mock.patch.object(ops, "gf_matmul_numpy",
                           wraps=ops.gf_matmul_numpy) as kernel:
        t0 = time.perf_counter()
        sim = FleetSimulator(sc, make_policy("ftr", engine=engine),
                             code_params(), seed=seed)
        m = sim.run()
        wall = time.perf_counter() - t0
    return sim, m, wall, kernel.call_count


def _reads_while_down(events) -> int:
    down = {}
    windows = []
    for e in events:
        if e["ev"] == "node_fail":
            down[e["node"]] = e["t"]
        elif e["ev"] == "repair_complete" and e["node"] in down:
            windows.append((down.pop(e["node"]), e["t"]))
    return sum(1 for e in events if e["ev"] == "read_queued"
               and any(lo <= e["t"] < hi for lo, hi in windows))


def _same_store(a, b) -> bool:
    return (np.array_equal(a.file_blocks, b.file_blocks)
            and sorted(a.nodes) == sorted(b.nodes)
            and all(np.array_equal(a.nodes[i].vectors, b.nodes[i].vectors)
                    and np.array_equal(a.nodes[i].payload, b.nodes[i].payload)
                    for i in a.nodes))


def phase_fleet(seed: int, cell: int = CELL) -> None:
    runs = {}
    for engine, matmul in (("jax", "kernel"), ("batched", "kernel"),
                           ("batched", "numpy")):
        sim, m, wall, kcalls = _fleet_run(seed, engine, matmul, cell)
        name = f"{engine}+{matmul}"
        runs[name] = sim
        during = _reads_while_down(sim.recorder.events)
        require(m.completed == len(FAILURES),
                f"{name}: {m.completed} repairs completed, "
                f"{len(FAILURES)} failures injected")
        require(m.decode_checks == len(FAILURES) and m.decode_failures == 0,
                f"{name}: {m.decode_failures} of {m.decode_checks} decode "
                f"checks failed")
        require(m.reads_completed > 0, f"{name}: no read completed")
        require(during > 0, f"{name}: no read arrived while a node was down")
        require((kcalls > 0) == (matmul == "kernel"),
                f"{name}: {kcalls} kernel calls")
        say("fleet", run=name, wall_s=f"{wall:.3f}", kernel_calls=kcalls,
            repairs=m.completed, decode_checks=m.decode_checks,
            decode_failures=m.decode_failures,
            reads_completed=m.reads_completed, reads_while_down=during)

    sim = runs["jax+kernel"]
    store = sim.dataplane.store
    repaired = [node for _, node in FAILURES]
    others = [i for i in sorted(store.nodes) if i not in repaired]
    combo = repaired + others[:K - len(repaired)]
    t0 = time.perf_counter()
    got = store.rl.reconstruct([store.nodes[i] for i in combo],
                               int(sim.dataplane.mini.M))
    dt = time.perf_counter() - t0
    require(np.array_equal(got, store.file_blocks),
            f"reconstruct from nodes {combo} does not give back the file")
    require(_same_store(runs["batched+kernel"].dataplane.store,
                        runs["batched+numpy"].dataplane.store),
            "kernel and NumPy coded stores differ")
    require(_same_store(store, runs["batched+kernel"].dataplane.store),
            "jax-planned and batched-planned coded stores differ")
    say("fleet", reconstruct_nodes=combo, reconstruct_s=f"{dt:.3f}",
        file_bytes=got.size, byte_exact="yes", stores_equal="yes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every overlay, store and fleet draw")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        raise SystemExit("chip_smoke: FAILED: no repro package under "
                         f"{os.path.join(HERE, 'src')}")
    sys.path.insert(0, os.path.join(HERE, "src"))
    warnings.simplefilter("error", RuntimeWarning)

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: FAILED: no TPU found ({e})")
    dev = devices[0]
    require(dev.platform == "tpu",
            f"no TPU found: JAX's first device is {dev.platform!r}")
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices))

    from repro.compile_cache import enable_compile_cache
    say("cache", dir=enable_compile_cache())
    clock = CompileClock()

    t0 = time.perf_counter()
    plan = phase_planner(args.seed, clock)
    say("planner", passed="yes", phase_s=f"{time.perf_counter() - t0:.1f}")
    t0 = time.perf_counter()
    phase_gf_kernel(args.seed, plan)
    say("gf_kernel", passed="yes", phase_s=f"{time.perf_counter() - t0:.1f}")
    t0 = time.perf_counter()
    phase_fleet(args.seed)
    say("fleet", passed="yes", phase_s=f"{time.perf_counter() - t0:.1f}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
