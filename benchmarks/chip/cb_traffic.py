"""The one generator of the benchmark's traffic, driven by a mix's data file.

A mix (``traffic/<name>.json``) is a closed loop of steps.  Each step
plans a batch of overlays with every scheme the mix names through the
program's ``plan_many(..., engine="jax")``; a mix with ``"repair":
true`` then runs the plan on the coded store, with the failed node's
fragment erased first.  Parameters:

- ``batch``: batch sizes, one drawn uniformly per step;
- ``schemes``: the planners each step calls, in order;
- ``draws``: ``null`` draws fresh overlays every step from the run's seed;
  a number R fixes R draws (overlay, failed node, helpers) from
  ``DRAW_SEED``, which the run's seed puts in another order and replays as
  often as the window needs, so every seed warms and runs the same shapes;
- ``repair``: whether each step runs its plan on the store;
- ``workers`` (optional, 1 to ``MAX_WORKERS``, default 1; repair mixes
  only): W closed-loop workers, each a thread with a block group and store
  of its own, replaying the same R draws in its own order, as a DataNode
  runs several block-group reconstructions at once (HDFS's
  ``dfs.datanode.ec.reconstruction.threads``, 8 by default).  Worker 0 is
  the one worker of ``workers`` 1: the same seeds, draws and shapes.

Overlays are the paper's evaluation setting (Section VI): every directed
link i.i.d. U[lo, hi], the configuration's ``capacity_lo``/``capacity_hi``.
"""
from __future__ import annotations

import copy
import dataclasses
import functools
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from cb_program import Program

Shape = Tuple[int, int, int]

#: the fixed draws of a mix with ``draws``; the run's seed orders them
DRAW_SEED = 20160517
#: columns of every GF product of the window kept for the output check
CHECK_COLUMNS = 64
#: the most workers a mix may run at once
MAX_WORKERS = 16
#: the window's records, each entry tagged with the worker that made it
RECORDS = ("plan_calls", "plan_records", "exec_s", "exec_mm_s",
           "mm_shapes", "mm_samples", "repaired")


def overlays(rng: np.random.Generator, batch: int, d: int, lo: float,
             hi: float) -> np.ndarray:
    """(batch, d+1, d+1) capacities, every directed link U[lo, hi]."""
    caps = rng.uniform(lo, hi, size=(batch, d + 1, d + 1))
    idx = np.arange(d + 1)
    caps[:, idx, idx] = 0.0
    return caps


def _stream(seed: int, key: int, worker: int) -> list:
    """The seed of one of a worker's random streams: worker 0 keeps the
    streams of a run with one worker."""
    return [seed, key] if worker == 0 else [seed, key, worker]


def data_file(seed: int, rows: int, cell: int, worker: int = 0
              ) -> np.ndarray:
    """A worker's block group, rows x cell random bytes from the seed."""
    rng = np.random.default_rng(_stream(seed, 2, worker))
    return np.frombuffer(rng.bytes(rows * cell), np.uint8).reshape(rows, cell)


def store_seed(seed: int, worker: int) -> int:
    """The seed of a worker's store (its coding coefficients), derived from
    its block group's stream."""
    if worker == 0:
        return seed
    return int(np.random.SeedSequence(_stream(seed, 2, worker))
               .generate_state(1, np.uint64)[0])


def workers_of(mix: dict) -> int:
    """The mix's ``workers``; ValueError outside 1..MAX_WORKERS."""
    w = mix.get("workers", 1)
    if type(w) is not int or not 1 <= w <= MAX_WORKERS:
        raise ValueError(f"workers must be a whole number from 1 to "
                         f"{MAX_WORKERS}, the mix says {w!r}")
    if w > 1 and not mix["repair"]:
        raise ValueError("workers above 1 need a repair mix")
    return w


@dataclasses.dataclass
class Worker:
    """One closed loop of a repair mix: its block group's store, its order
    of the fixed draws and its stream of kernel sample columns.  Only its
    own thread touches it."""
    index: int
    sample_rng: np.random.Generator
    order: Optional[np.ndarray] = None
    store: object = None
    steps: int = 0
    step_mm_s: float = 0.0           # GF matmul seconds of the step so far


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Traffic:
    """Set-up, one step at a time, and what the window recorded."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 program: Optional[Program] = None):
        self.config, self.mix, self.seed = config, mix, seed
        self.workers = [
            Worker(w, np.random.default_rng(_stream(seed, 1, w)))
            for w in range(workers_of(mix))]
        self.program = program if program is not None else Program(config)
        self.n, self.k, self.d = config["n"], config["k"], config["d"]
        self.alpha, self.M = config["alpha"], config["M"]
        self.cell = config["cell_bytes"]
        self.rng = np.random.default_rng([seed, 0])
        self.recording = False
        self.steps = 0
        # records of the window, appended under the lock
        self.lock = threading.Lock()
        self.plan_calls: List[Tuple[str, int, float]] = []
        self.plan_records: List[Tuple[str, np.ndarray, object]] = []
        self.repaired: List[int] = []
        self.exec_s: List[float] = []
        # the GF matmul seconds inside each completed repair
        self.exec_mm_s: List[float] = []
        self.mm_shapes: List[Shape] = []
        self.mm_samples: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        self.worker_of: Dict[str, List[int]] = {r: [] for r in RECORDS}
        self.draws = None
        self.warmed_shapes: List[Shape] = []
        self.traced: Optional["Traffic"] = None

    # -- the store's matmul: the program's kernel, with spans and samples --

    def _record(self, worker: Worker, **entries) -> None:
        with self.lock:
            for name, entry in entries.items():
                getattr(self, name).append(entry)
                self.worker_of[name].append(worker.index)

    def matmul(self, a: np.ndarray, b: np.ndarray,
               worker: Worker) -> np.ndarray:
        t0 = time.perf_counter()
        with _span("bench.gf_matmul"):
            out = self.program.kernel_matmul(a, b)
        dt = time.perf_counter() - t0
        if self.recording:
            worker.step_mm_s += dt
            n = b.shape[1]
            s = min(n, CHECK_COLUMNS)
            cols = np.sort(worker.sample_rng.choice(n, s, replace=False))
            self._record(worker, mm_shapes=(*a.shape, n),
                         mm_samples=(np.array(a, np.uint8), b[:, cols],
                                     np.asarray(out)[:, cols]))
        return out

    # -- set-up -------------------------------------------------------------

    def setup(self, say: Callable[..., None]) -> None:
        mix = self.mix
        if mix["draws"] is not None:
            drng = np.random.default_rng(DRAW_SEED)
            self.draws = []
            for _ in range(mix["draws"]):
                caps = overlays(drng, 1, self.d, self.config["capacity_lo"],
                                self.config["capacity_hi"])
                failed = int(drng.integers(self.n))
                helpers = [int(x) for x in drng.permutation(
                    [j for j in range(self.n) if j != failed])[:self.d]]
                self.draws.append((caps, failed, helpers))
            for w in self.workers:
                w.order = np.random.default_rng(
                    _stream(self.seed, 3, w.index)).permutation(
                        len(self.draws))
        t0 = time.perf_counter()
        plans = self._warm_planner()
        say("setup", part="planner_warmup", seconds=time.perf_counter() - t0)
        if mix["repair"]:
            t0 = time.perf_counter()
            for w in self.workers:
                with _span("bench.store_build"):
                    w.store = self.program.store(
                        data_file(self.seed, self.M, self.cell, w.index),
                        store_seed(self.seed, w.index),
                        functools.partial(self.matmul, worker=w))
            say("setup", part="store_build", seconds=time.perf_counter() - t0,
                store_bytes=self.n * self.alpha * self.cell,
                stores=len(self.workers))
            t0 = time.perf_counter()
            self._warm_kernel(plans)
            say("setup", part="gf_shape_warmup",
                seconds=time.perf_counter() - t0,
                shapes=len(self.warmed_shapes))

    def _warm_planner(self):
        """Plan every shape the window plans; return the fixed draws' plans."""
        if self.draws is not None:
            plans = []
            for caps, _, _ in self.draws:
                for scheme in self.mix["schemes"]:
                    res = self.program.plan(caps, scheme)
                plans.append(self.program.plans(res)[0])
            return plans
        wrng = np.random.default_rng([self.seed, 4])
        for B in sorted(set(self.mix["batch"])):
            caps = overlays(wrng, B, self.d, self.config["capacity_lo"],
                            self.config["capacity_hi"])
            for scheme in self.mix["schemes"]:
                self.program.plan(caps, scheme)
        return None

    def _warm_kernel(self, plans) -> None:
        """Run every GF shape the draws' repairs issue once, on zeros.

        The shapes follow from the plans alone, so a shadow store one byte
        wide, with a matmul that only records shapes, finds them."""
        found = set()

        def shape_only(a, b):
            found.add((a.shape[0], a.shape[1], b.shape[1]))
            return np.zeros((a.shape[0], b.shape[1]), np.uint8)

        shadow = self.program.store(data_file(self.seed, self.M, 1),
                                    self.seed, shape_only)
        found.clear()                  # the encode of the block group
        for (_, failed, helpers), plan in zip(self.draws, plans):
            shadow.nodes.pop(failed, None)
            shadow.execute_plan(plan, failed, helpers)
        for m, k, n in sorted(found):
            n = self.cell if n == 1 else n
            self.matmul(np.zeros((m, k), np.uint8), np.zeros((k, n), np.uint8),
                        self.workers[0])
            self.warmed_shapes.append((m, k, n))

    # -- one step -------------------------------------------------------------

    def _plan(self, caps: np.ndarray, scheme: str, worker: Worker):
        t0 = time.perf_counter()
        with _span("bench.plan_many"):
            res = self.program.plan(caps, scheme)
        dt = time.perf_counter() - t0
        if self.recording:
            self._record(worker, plan_calls=(scheme, caps.shape[0], dt),
                         plan_records=(scheme, caps, res))
        return res

    def step(self, w: Worker) -> None:
        """One step of worker ``w``, on its store."""
        mix = self.mix
        w.step_mm_s = 0.0
        with _span("bench.step"):
            if self.draws is not None:
                caps, failed, helpers = self.draws[
                    w.order[w.steps % len(self.draws)]]
            else:
                B = int(mix["batch"][self.rng.integers(len(mix["batch"]))])
                caps = overlays(self.rng, B, self.d,
                                self.config["capacity_lo"],
                                self.config["capacity_hi"])
            for scheme in mix["schemes"]:
                res = self._plan(caps, scheme, w)
            if mix["repair"]:
                plan = self.program.plans(res)[0]
                t0 = time.perf_counter()
                with _span("bench.execute_plan"):
                    w.store.nodes.pop(failed, None)  # the node is lost
                    w.store.execute_plan(plan, failed, helpers)
                dt = time.perf_counter() - t0
                if self.recording:
                    self._record(w, exec_s=dt, exec_mm_s=w.step_mm_s,
                                 repaired=failed)
        w.steps += 1
        with self.lock:
            self.steps += 1

    # -- what the window did ----------------------------------------------------

    def mark_traced(self) -> None:
        """Keep, as ``self.traced``, what the traced part of the window did:
        a copy of the records so far, for the per-layer readers."""
        with self.lock:
            t = copy.copy(self)
            for name in RECORDS:
                setattr(t, name, list(getattr(self, name)))
            t.worker_of = {k: list(v) for k, v in self.worker_of.items()}
        self.traced = t

    @property
    def repairs(self) -> int:
        """Repairs the window completed, over every worker."""
        return len(self.repaired)

    def repairs_by_worker(self) -> List[int]:
        return [self.worker_of["repaired"].count(w.index)
                for w in self.workers]

    def operations(self) -> int:
        return self.repairs if self.mix["repair"] else len(self.plan_calls)

    def plans_returned(self) -> int:
        return sum(B for _, B, _ in self.plan_calls)
