"""Repair-round simulation (paper Section VI + Appendix A Fig. 10).

Two levels of fidelity:

* ``compare_schemes`` — planning-level Monte Carlo: per round, sample an
  overlay, plan with each scheme, record regeneration time and total repair
  traffic normalized against STAR on the *same* network (Figs 6-8).
* ``RlncSimulator`` — data-plane simulation with real GF coding vectors:
  executes plans block-by-block (provider encode, interior relay, newcomer
  regenerate) and measures the probability that k random nodes can still
  reconstruct the file (Fig. 10, RCTREE's MDS collapse).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import random
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.coding import GF, GF8, RLNC, CodedBlocks
from repro.core import (CodeParams, RepairPlan, caps_tensor, get_scheme,
                        plan, plan_many, plans_from_batch)
from repro.obs.spans import span
from .capacities import CapSampler


# ---------------------------------------------------------------------------
# Planning-level Monte Carlo (Figs 6-8)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SchemeStats:
    scheme: str
    mean_time: float
    mean_norm_time: float      # vs STAR on the same sampled network
    mean_traffic: float
    mean_norm_traffic: float
    plan_seconds: float        # mean planner wall time
    engine: str = "scalar"     # engine that actually planned this scheme


def compare_schemes(params: CodeParams, sampler: CapSampler,
                    schemes: Sequence[str], trials: int,
                    seed: int = 0, engine: str = "batched",
                    witness: str = "exact",
                    ) -> Dict[str, SchemeStats]:
    """Monte-Carlo scheme comparison over ``trials`` sampled overlays.

    All planning is dispatched through :func:`repro.core.plan_many` /
    :func:`repro.core.plan`, so engine selection, per-scheme kwarg
    forwarding (``witness`` reaches exactly the schemes that declared it)
    and the scalar fallback for registry entries without a batched planner
    (rctree) are owned by the scheme registry — the fallback warns once per
    scheme per process and is surfaced in ``SchemeStats.engine``.
    ``engine="batched"`` (default) plans every trial at once;
    ``engine="jax"`` routes jax-capable schemes through the jit tier
    (others fall back per the registry, with its once-per-scheme warning)
    while the STAR normalization baseline stays on the batched engine so
    normalized metrics are engine-for-engine comparable;
    ``engine="scalar"`` is the original per-network loop, kept as the
    correctness oracle (see tests/test_batched.py).  ``witness`` selects
    the traffic-minimal witness engine for fr/ftr: the exact level-cut
    oracle (default) or the per-trial scipy LP (``witness="lp"``).
    """
    import time as _time

    if engine not in ("batched", "scalar", "jax"):
        raise ValueError(f"unknown engine {engine!r}")
    rng = random.Random(seed)
    nets = [sampler(rng, params.d) for _ in range(trials)]

    if engine in ("batched", "jax"):
        caps = caps_tensor(nets)
        base = plan_many(caps, params, "star", engine="batched")
        out: Dict[str, SchemeStats] = {}
        for s in schemes:
            t0 = _time.perf_counter()
            res = plan_many(caps, params, s, engine=engine,
                            witness=witness)
            dt = _time.perf_counter() - t0
            out[s] = SchemeStats(
                s, float(res.times.mean()),
                float((res.times / base.times).mean()),
                float(res.traffic.mean()),
                float((res.traffic / base.traffic).mean()), dt / trials,
                engine=res.engine)
        return out

    acc = {s: [0.0, 0.0, 0.0, 0.0, 0.0] for s in schemes}
    for net in nets:
        base = plan(net, params, "star", engine="scalar")
        for s in schemes:
            t0 = _time.perf_counter()
            p = plan(net, params, s, engine="scalar", witness=witness)
            dt = _time.perf_counter() - t0
            a = acc[s]
            a[0] += p.time
            a[1] += p.time / base.time
            a[2] += p.total_traffic
            a[3] += p.total_traffic / base.total_traffic
            a[4] += dt
    return {
        s: SchemeStats(s, a[0] / trials, a[1] / trials, a[2] / trials,
                       a[3] / trials, a[4] / trials)
        for s, a in acc.items()
    }


# ---------------------------------------------------------------------------
# Data-plane simulation with real coding vectors (Fig. 10)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Reconstruction:
    """One ``execute_plan`` of a store: its start and end on
    ``time.perf_counter``, the coded rows the newcomer received and the
    payload bytes the repair gave the GF matmul as operands."""
    failed: int
    start_s: float
    end_s: float
    rows_received: int
    payload_bytes: int


def _owner(a: np.ndarray) -> np.ndarray:
    """The array that owns the memory ``a`` views."""
    while isinstance(a.base, np.ndarray):
        a = a.base
    return a


class RlncSimulator:
    """Distributed storage system with actual RLNC state per node.

    ``reconstructions`` counts the store's repairs, one
    :class:`Reconstruction` per ``execute_plan``; a store belongs to one
    thread at a time, so the list takes no lock."""

    def __init__(self, params: CodeParams, field: GF = GF8,
                 block_bytes: int = 4, seed: int = 0,
                 matmul: Optional[Callable] = None, engine: str = "batched"):
        if abs(params.M - round(params.M)) > 1e-9 or \
           abs(params.alpha - round(params.alpha)) > 1e-9:
            raise ValueError("data-plane simulation needs integral M, alpha")
        if engine not in ("batched", "scalar"):
            raise ValueError(f"unknown engine {engine!r}")
        self.params = params
        self.engine = engine
        self.field = field
        self.rl = RLNC(field, matmul=matmul)
        self.np_rng = np.random.default_rng(seed)
        self.rng = random.Random(seed + 1)
        M, n, alpha = int(params.M), params.n, int(round(params.alpha))
        self.file_blocks = field.random((M, block_bytes), self.np_rng)
        self.nodes: Dict[int, CodedBlocks] = dict(
            enumerate(self.rl.distribute(self.file_blocks, n, alpha,
                                         self.np_rng)))
        self.reconstructions: List[Reconstruction] = []

    def host_bytes(self) -> int:
        """Host bytes the nodes' rows hold: every array that owns memory
        under their coding vectors and payloads, counted once and whole."""
        owners = {}
        for blocks in self.nodes.values():
            for a in (blocks.vectors, blocks.payload):
                o = _owner(a)
                owners[id(o)] = o.nbytes
        return sum(owners.values())

    def execute_plan(self, plan: RepairPlan, failed: int,
                     provider_ids: Sequence[int]) -> None:
        """Replace ``failed`` by running ``plan`` on the real coded state.

        Fractional betas/flows are ceil-rounded (Section III-C).  For the
        broken RCTREE baseline, flows are the plan's fixed per-edge beta,
        which is what destroys information at interior nodes.

        A node's pool is a list of parts, its children's then its own,
        joined only where a GF matmul reads a pool of several parts: at a
        relaying node and at the newcomer.  A pool within its edge flow
        goes up as it is.

        Spans: ``repro.execute_plan`` around the call, with args ``rows``
        (coded rows the newcomer received) and ``payload_bytes`` (payload
        bytes given to the GF matmul), ``repro.store.node`` around each
        tree node's work, the newcomer's included (a node's span holds
        those of its children), and ``repro.store.concat`` around each
        join.  The same numbers, with the call's start and end, are
        appended to ``reconstructions``.
        """
        start = time.perf_counter()
        with span("execute_plan") as s, span("store.node"):
            rows, payload_bytes = self._execute_plan(plan, failed,
                                                     provider_ids)
            s.set_metadata(rows=rows, payload_bytes=payload_bytes)
        self.reconstructions.append(Reconstruction(
            failed, start, time.perf_counter(), rows, payload_bytes))

    def _execute_plan(self, plan: RepairPlan, failed: int,
                      provider_ids: Sequence[int]) -> tuple[int, int]:
        """The repair; returns the rows the newcomer received and the
        payload bytes given to the matmul."""
        alpha = int(round(self.params.alpha))
        payload_bytes = 0

        def encode(blocks: CodedBlocks, num_out: int) -> CodedBlocks:
            nonlocal payload_bytes
            payload_bytes += blocks.payload.nbytes
            return self.rl.encode(blocks, num_out, self.np_rng)

        idmap = {i: pid for i, pid in enumerate(provider_ids, start=1)}
        children: Dict[int, List[int]] = {}
        for u, p in plan.parent.items():
            children.setdefault(p, []).append(u)

        def produce(u: int) -> List[CodedBlocks]:
            """Blocks node u sends to its tree parent, as parts in order:
            its children's (in ``plan.parent`` order), then its own."""
            pool: List[CodedBlocks] = []
            for ch in children.get(u, []):
                with span("store.node"):
                    pool += produce(ch)
            send_quota = int(math.ceil(plan.flows[(u, plan.parent[u])] - 1e-9))
            own = encode(self.nodes[idmap[u]],
                         int(math.ceil(plan.betas[u - 1] - 1e-9)))
            if not pool:
                # a leaf: cap at the plan's edge flow (RCTREE keeps this
                # below alpha)
                return [CodedBlocks(own.vectors[:send_quota],
                                    own.payload[:send_quota])]
            pool.append(own)
            if sum(p.num for p in pool) <= send_quota:
                return pool                # forwarded unjoined, within the flow
            # relay: recode the pool, joined once, down to the edge flow
            return [encode(CodedBlocks.join(pool), send_quota)]

        received: List[CodedBlocks] = []
        for r in children.get(0, []):
            with span("store.node"):
                received += produce(r)
        assert received
        pool = CodedBlocks.join(received)
        payload_bytes += pool.payload.nbytes
        self.nodes[failed] = self.rl.regenerate(pool, alpha, self.np_rng)
        return pool.num, payload_bytes

    def _sample_round(self, sampler: CapSampler,
                      failed: Optional[int] = None):
        """(failed, providers, overlay) for one repair round.

        Draws only from ``self.rng`` — the data-plane ``np_rng`` is a
        separate stream, so rounds may be pre-sampled in bulk (for batched
        planning) without perturbing execution randomness.  Anything else
        drawing from ``self.rng`` between rounds (subset-sampled
        ``reconstruction_probability``) IS perturbed by bulk pre-sampling;
        see ``reconstruction_vs_rounds``."""
        ids = sorted(self.nodes)
        if failed is None:
            failed = self.rng.choice(ids)
        survivors = [i for i in ids if i != failed]
        providers = self.rng.sample(survivors, self.params.d)
        net = sampler(self.rng, self.params.d)
        return failed, providers, net

    def plan_rounds(self, scheme: str, sampler: CapSampler,
                    rounds: int) -> List:
        """Pre-sample ``rounds`` repair rounds and plan them all.

        With the batched engine this is ONE ``plan_batch`` call for the
        whole trial (plans depend only on the sampled overlays, never on
        the coded state); schemes without a batched planner (rctree) use
        the scalar loop.  Returns [(failed, providers, plan), ...] ready
        for ``execute_plan``.
        """
        drawn = [self._sample_round(sampler) for _ in range(rounds)]
        # engine="auto" rides the batched planner when the registry has one
        # and silently takes the scalar oracle otherwise (rctree)
        eng = "auto" if self.engine == "batched" else "scalar"
        res = plan_many([net for _, _, net in drawn], self.params, scheme,
                        engine=eng)
        plans = plans_from_batch(res, self.params)
        return [(f, p, pl) for (f, p, _), pl in zip(drawn, plans)]

    def repair_round(self, scheme: str, sampler: CapSampler,
                     failed: Optional[int] = None) -> RepairPlan:
        failed, providers, net = self._sample_round(sampler, failed)
        eng = "auto" if self.engine == "batched" else "scalar"
        pl = plans_from_batch(plan_many([net], self.params, scheme,
                                        engine=eng), self.params)[0]
        self.execute_plan(pl, failed, providers)
        return pl

    def reconstruction_probability(self, samples: int = 0) -> float:
        """Fraction of k-subsets (all, or ``samples`` random ones) whose
        combined coding vectors have rank >= M."""
        ids = sorted(self.nodes)
        k, M = self.params.k, int(self.params.M)
        combos = list(itertools.combinations(ids, k))
        if samples and samples < len(combos):
            combos = self.rng.sample(combos, samples)
        ok = 0
        for combo in combos:
            if self.rl.can_reconstruct([self.nodes[i] for i in combo], M):
                ok += 1
        return ok / len(combos)


def reconstruction_vs_rounds(params: CodeParams, scheme: str,
                             sampler: CapSampler, rounds: int, trials: int,
                             field: GF = GF8, seed: int = 0,
                             subset_samples: int = 0,
                             engine: str = "batched") -> List[float]:
    """Fig. 10: mean reconstruction probability after each repair round.

    Planning runs on the batched engine by default: each trial's rounds are
    pre-sampled and planned in ONE ``plan_batch`` call (the plan depends
    only on the sampled overlay, never on the coded state, and the overlay
    rng is a separate stream from the data-plane rng — so the round-by-round
    scalar oracle, ``engine="scalar"``, produces identical node states).

    The bulk path requires that nothing else consumes ``sim.rng`` between
    rounds: with ``subset_samples > 0``, ``reconstruction_probability``
    draws k-subsets from that same stream, so bulk pre-sampling would
    reorder the draws and diverge from the oracle — those calls (and
    schemes without a batched planner, e.g. rctree) use the round-by-round
    loop instead, which preserves the stream order exactly."""
    probs = [0.0] * (rounds + 1)
    for tr in range(trials):
        sim = RlncSimulator(params, field=field, seed=seed + 1000 * tr,
                            engine=engine)
        probs[0] += sim.reconstruction_probability(subset_samples)
        if (engine == "batched" and subset_samples == 0
                and get_scheme(scheme).batched is not None):
            planned = sim.plan_rounds(scheme, sampler, rounds)
            for r, (failed, providers, plan) in enumerate(planned, start=1):
                sim.execute_plan(plan, failed, providers)
                probs[r] += sim.reconstruction_probability(subset_samples)
        else:
            for r in range(1, rounds + 1):
                sim.repair_round(scheme, sampler)
                probs[r] += sim.reconstruction_probability(subset_samples)
    return [p / trials for p in probs]
