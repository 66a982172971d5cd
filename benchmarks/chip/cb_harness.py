"""One run of one cell: set-up, the measured window, metrics and the check.

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/<config>.json``, its
traffic mix in ``traffic/<traffic>.json`` and each metric's reader in
``metrics/<metric>.py`` (for ``<base>.<kind>``, else ``metrics/<base>.py``).
Nothing here names a cell, configuration, mix or metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: a traced run traces the window's first steps, up to the first that ends
#: after this many seconds: reading a trace of the planner's while loops
#: takes about 8 s per traced second, and a run must end within 360 s
TRACE_SECONDS = 6.0


class Refused(SystemExit):
    """The run cannot measure: exit non-zero and print no result."""

    def __init__(self, why: str):
        super().__init__(f"chip benchmark: {why}")


def say(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with a trace its per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str) -> Callable:
    """``metrics/<name>.py``; a metric split by cell kind, ``<base>.<kind>``,
    falls back to the shared ``metrics/<base>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(HERE, "metrics", f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        "cb_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileClock:
    """Compiles, persistent-cache loads and traces that JAX reports through
    its monitoring events (copied from the repository's smoke test)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def counts(self) -> tuple:
        return self.compiles, self.cache_hits, self.traces


@dataclasses.dataclass
class Run:
    """What a metric's reader reads: the window's records, or in a traced
    run those of its traced part, with that part's trace summary."""
    traffic: object
    window_s: float
    setup_s: float
    device_kind: str
    summary: object = None             # cb_trace.Summary of a traced run
    say: Callable = say


def use_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for every
    program of every size; set before JAX is imported, so it also holds for
    the program's own code."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def accelerator(chips: int):
    """The devices JAX found; anything but enough TPUs refuses the run."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no accelerator ({e})")
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU, only {devices[0].platform!r}")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found "
                      f"{len(devices)}")
    return devices


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_cell(name: str):
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(bench_path):
        raise Refused(f"no BENCHMARK.json at {ROOT}")
    bench = load_json(bench_path)
    cell = find_cell(bench, name)
    config = load_json(os.path.join(HERE, "configs", f"{cell['config']}.json"))
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return bench, cell, config, mix


def import_program() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"the system under test is missing: no {src}/repro")
    if src not in sys.path:
        sys.path.insert(0, src)


def measure(traffic, seconds: float, trace_dir: Optional[str]):
    """Steps until ``seconds`` have passed; each worker then ends the step
    it is in, and the window ends when the last one stops.  One worker
    steps in this thread, several each in a thread of its own.

    With ``trace_dir`` the profiler traces the window's first steps inside
    a ``bench.window`` span: each worker ends its traced part with the step
    it is in once ``TRACE_SECONDS`` have passed, and waits there until the
    last has ended its own; then the records are copied and the profiler
    stops.  So the trace holds whole steps, and the copy holds them all.
    Returns the window's length and start."""
    import jax
    tracing = threading.Condition()
    in_trace = set()                   # workers still in the traced part
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
        window = jax.profiler.TraceAnnotation("bench.window")
        window.__enter__()
        in_trace = {w.index for w in traffic.workers}

    def leave_trace(worker) -> None:
        with tracing:
            if worker.index not in in_trace:
                return
            in_trace.discard(worker.index)
            if not in_trace:
                window.__exit__(None, None, None)
                traffic.mark_traced()
                jax.profiler.stop_trace()
                tracing.notify_all()
            while in_trace:
                tracing.wait()

    def loop(worker) -> float:
        try:
            while True:
                traffic.step(worker)
                elapsed = time.perf_counter() - t0
                if elapsed >= min(TRACE_SECONDS, seconds):
                    leave_trace(worker)
                if elapsed >= seconds:
                    return elapsed
        finally:
            leave_trace(worker)        # a worker that fails holds no other

    traffic.recording = True
    t0 = time.perf_counter()
    if len(traffic.workers) == 1:
        elapsed = loop(traffic.workers[0])
    else:
        with ThreadPoolExecutor(len(traffic.workers),
                                thread_name_prefix="bench.worker") as pool:
            futures = [pool.submit(loop, w) for w in traffic.workers]
            elapsed = max([f.result() for f in futures])
    traffic.recording = False
    return elapsed, t0


def run(argv, t_process: float) -> dict:
    """One run; returns the result line's object (also printed)."""
    args = parse(argv)
    bench, cell, config, mix = load_cell(args.workload)
    import_program()
    t0 = time.perf_counter()
    use_compile_cache()
    devices = accelerator(cell["chips"])
    dev = devices[0]
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), jax_init_s=time.perf_counter() - t0,
        cache=CACHE_DIR)
    return run_on(args, bench, cell, config, mix, devices, t_process)


def run_on(args, bench, cell, config, mix, devices, t_process: float,
           program=None, check_stream=None) -> dict:
    import cb_check
    import cb_trace
    from cb_traffic import Traffic

    dev = devices[0]
    clock = CompileClock()
    try:
        traffic = Traffic(config, mix, args.seed, program=program)
    except ValueError as e:            # a mix or configuration it cannot run
        raise Refused(str(e))
    traffic.setup(say)
    c0 = clock.counts()
    say("setup", part="compiles", backend_compiles=c0[0],
        compile_s=clock.compile_s, cache_loads=c0[1], traces=c0[2])
    trace_dir = tempfile.mkdtemp(prefix="cb_trace_") if args.trace else None
    try:
        window_s, t_window = measure(traffic, args.seconds, trace_dir)
        setup_s = t_window - t_process
        c1 = clock.counts()
        in_window = [b - a for a, b in zip(c0, c1)]
        by_worker = ",".join(map(str, traffic.repairs_by_worker()))
        say("window", seconds=window_s, workers=len(traffic.workers),
            steps=traffic.steps, repairs=traffic.repairs,
            repairs_by_worker=by_worker,
            plan_calls=len(traffic.plan_calls),
            plans=traffic.plans_returned(),
            gf_calls=len(traffic.mm_shapes),
            gf_shapes=len(set(traffic.mm_shapes)),
            gf_shapes_warmed=len(traffic.warmed_shapes),
            compiles_in_window=in_window[0], cache_loads_in_window=in_window[1],
            traces_in_window=in_window[2])
        summary = None
        if trace_dir is not None:
            summary = cb_trace.reduce(cb_trace.load(
                cb_trace.find_xplane(trace_dir)))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    # per-layer readers read the traced part of the window
    rec = Run(traffic.traced if args.trace else traffic, window_s, setup_s,
              dev.device_kind, summary)
    metrics = {}
    for m in metrics_of(bench, cell["name"], bool(args.trace)):
        value = reader(m["name"])(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    t0 = time.perf_counter()
    numbers, wrong = cb_check.check(traffic, args.seed)
    correct = cb_check.within(numbers)
    say("check", seconds=time.perf_counter() - t0, correct=correct)
    stream = check_stream if check_stream is not None else sys.stderr
    for name, value, limit in numbers:
        print(f"check {name}={value!r} limit={limit!r}", file=stream,
              flush=True)
    line = {"correct": correct, "attempted": traffic.operations(),
            "failed": wrong, "metrics": metrics, "device": device}
    if summary is not None:
        line["breakdown"] = {"device_ops": summary.top_ops(),
                             "idle_gaps": summary.top_gaps()}
    # the numbers compared, each with its limit, come last
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit in numbers}
    print(json.dumps(line), flush=True)
    return line
