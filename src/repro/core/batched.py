"""Batched planning engine: FR/TR/FTR across many overlay networks at once.

The paper's Monte-Carlo sweeps (Figs. 6-8) evaluate each sampled network with
scalar planners — bisection with a per-step feasibility oracle, per-candidate
LP calls, and ``plan_tr``'s greedy that re-derives tree flows from scratch at
every candidate edge.  This module re-expresses the whole pipeline as NumPy
array programs over a batch axis so that *all trials advance in lockstep*:

* ``minmax_time_star_batch`` — the Theorem-1 star bisection of problem (1) on
  ``(trials, d)`` arrays: one sort + cumsum per bisection step replaces
  ``trials * k`` Python-level sigma evaluations.
* ``plan_tr_batch`` — Algorithm 1 with *incremental* state: per step the
  engine maintains subtree sizes and ancestor masks, so each candidate edge
  is scored with O(1) array lookups instead of an O(d) flow recomputation
  (the scalar greedy is O(d^3) oracle work per network; the batched greedy is
  d lockstep steps of O(d^2) vector math shared by every trial).
* ``plan_ftr_batch`` — Algorithm 2 evaluating all i = 0..d core trees plus
  the TR candidate as one batched population, with the same incumbent
  pruning, pivot local search and final high-precision solve as the scalar
  planner.  The per-tree feasibility oracle is a lockstep water-fill
  (``waterfill_batch``) over laminar subtree caps.

The scalar planners in ``star.py`` / ``tree.py`` / ``ftr.py`` remain the
correctness oracle: ``tests/test_batched.py`` asserts equivalence on seeded
random networks across MSR/interior/MBR operating points.  Nothing stays
scalar per trial: the traffic-minimal witness at the optimal time — once a
HiGHS LP per trial (~1.6 ms each, ~40% of the fig6 d=6 row) — is now the
exact batched level-cut oracle (``repro.core.witness``); ``witness="lp"``
on ``plan_fr_batch`` / ``plan_ftr_batch`` restores the per-trial LP loop.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from . import lp
from . import witness as wit
from .ftr import (EVAL_ITERS as _EVAL_ITERS, FINAL_ITERS as _FINAL_ITERS,
                  LOCAL_SEARCH_ALTS as _MAX_ALTS,
                  LOCAL_SEARCH_ROUNDS as _MAX_ROUNDS,
                  PROBE_SLACK as _PROBE_SLACK, REFINE_ITERS as _REFINE_ITERS)
from .lp import BISECT_ITERS as _STAR_ITERS
from .params import CodeParams, OverlayNetwork
from .regions import FeasibleRegion, heuristic_region, msr_region

_SPEC_LEVELS = 3            # bisection levels speculated per oracle call
_SPEC_NN = (1 << _SPEC_LEVELS) - 1   # rows per speculated bisection tree


def _pstage(profile, name: str):
    """Stage-timing context for the ``profile=`` hook (ISSUE 7).

    ``profile`` is any object with the ``repro.obs.profile.PlannerProfile``
    contract (duck-typed — this module never imports the obs package), or
    None for the zero-overhead default.  Timing wraps whole stages, so a
    profiled plan is the same plan: stages only measure, never branch.
    """
    if profile is None:
        return contextlib.nullcontext()
    return profile.stage(name)


# ---------------------------------------------------------------------------
# Batch containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BatchPlanResult:
    """Vectorized counterpart of a list of :class:`RepairPlan`.

    ``parents[b, u]`` is the tree parent of provider u (parents[b, 0] = 0);
    ``betas[b, i]`` the traffic generated by provider i+1; ``times`` /
    ``traffic`` match ``RepairPlan.time`` / ``RepairPlan.total_traffic``.

    ``engine`` records which planner produced the batch: ``"batched"`` for
    the array programs in this module, ``"jax"`` for the jit-compiled
    :mod:`repro.core.jax_engine` tier, ``"scalar"`` when
    :func:`repro.core.api.plan_many` fell back to the per-network scalar
    planner — in which case ``plans`` carries the original scalar
    :class:`RepairPlan` objects (some schemes, e.g. rctree, have flows that
    cannot be re-derived from (parents, betas); ``plans_from_batch`` returns
    them verbatim instead of reconstructing).  A ragged mixed-``d`` call
    reports ``"mixed"`` only if its buckets resolved to different engines.
    """

    scheme: str
    times: np.ndarray          # (B,)
    traffic: np.ndarray        # (B,)
    betas: np.ndarray          # (B, d)
    parents: np.ndarray        # (B, d+1)
    lower_bounds: Optional[np.ndarray] = None
    engine: str = "batched"
    plans: Optional[list] = None


def caps_tensor(nets: Sequence[OverlayNetwork]) -> np.ndarray:
    """Stack overlay capacity matrices into a (B, d+1, d+1) tensor."""
    return np.stack([np.asarray(n.cap, dtype=np.float64) for n in nets])


# ---------------------------------------------------------------------------
# Tree encodings
# ---------------------------------------------------------------------------

def subtree_masks(parents: np.ndarray) -> np.ndarray:
    """Subtree membership masks for batches of parent-index trees.

    ``parents`` is (..., d+1) int (entry u = parent of node u, root 0 maps to
    itself).  Returns float64 (..., d+1, d) where entry [..., u, x-1] = 1 iff
    provider x lies in the subtree rooted at u.  Computed as the transitive
    closure of the parent relation by log2(d) squarings of the one-step
    reachability matrix (pointer doubling — O(log d) batched matmuls instead
    of a d-step gather walk).
    """
    shape = parents.shape
    D1 = shape[-1]
    p = parents.reshape(-1, D1)
    P = p.shape[0]
    C = np.zeros((P, D1, D1))
    rows = np.arange(P)[:, None]
    node = np.arange(D1)[None, :]
    C[rows, node, node] = 1.0
    C[rows, node[:, 1:], p[:, 1:]] = 1.0
    steps = 1
    while steps < D1:
        C = (C @ C) > 0
        C = C.astype(np.float64)
        steps *= 2
    # C[x, u] = u lies on x's ancestor chain  =>  x in subtree(u)
    mask = np.ascontiguousarray(C.transpose(0, 2, 1)[:, :, 1:])
    return mask.reshape(shape + (D1 - 1,))


def _tree_arrays(caps: np.ndarray, parents: np.ndarray,
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(subtree masks, per-edge capacities) for a batch of full trees.

    ``edge_caps[b, u-1]`` = c(u, parent(u)) — capacity of provider u's uplink.
    """
    mask = subtree_masks(parents)
    B, D1 = parents.shape
    bidx = np.arange(B)[:, None]
    u = np.arange(1, D1)[None, :]
    edge_caps = caps[bidx, u, parents[:, 1:]]
    return mask, edge_caps


# ---------------------------------------------------------------------------
# Lockstep water-fill oracle (vectorized lp.waterfill_max)
# ---------------------------------------------------------------------------

def waterfill_batch(inc: np.ndarray, bnd: np.ndarray, alpha: float,
                    chain: Optional[np.ndarray] = None) -> np.ndarray:
    """Leximin-maximal vectors under uniform per-coordinate cap ``alpha`` and
    per-lane laminar set caps.

    ``inc`` is (P, S, d) 0/1 membership, ``bnd`` (P, S) bounds (+inf marks an
    inactive set), ``chain`` the optional precomputed (P, S, S) nesting
    relation (sets overlap iff nested, because the family is laminar).

    Computes the same unique leximin point as :func:`repro.core.lp.
    waterfill_max`, but freezes every *chain-minimal* saturated set per round
    instead of one global minimum: sets on disjoint subtrees never interact,
    and saturation levels only rise as freezing proceeds, so the fill order
    is immaterial — rounds drop from O(#binding sets) to O(nesting depth).
    Lanes are fully independent, so results are invariant to batch
    composition.
    """
    P, S, d = inc.shape
    if chain is None:
        chain = _nest_of(inc)
    athr = alpha - 1e-15
    v = np.zeros((P, d))
    active = np.ones((P, d))          # 0/1 floats; finished lanes self-noop
    X = np.empty((P, d, 2))
    with np.errstate(divide="ignore", invalid="ignore"):
        while True:
            X[..., 0] = active
            X[..., 1] = v * (1.0 - active)
            Y = inc @ X               # one batched matmul: (active counts, frozen sums)
            na = Y[..., 0]
            cand = (bnd - Y[..., 1]) / np.maximum(na, 1.0)
            np.copyto(cand, np.inf, where=na == 0)
            freezable = cand < athr
            if not freezable.any():
                # terminal round everywhere: water reached the alpha cap
                np.copyto(v, alpha, where=active > 0)
                break
            # a set freezes when no nested (ancestor/descendant) set has a
            # lower saturation level; equal levels freeze together
            chmin = np.where(chain, cand[:, None, :], np.inf).min(axis=2)
            setfreeze = freezable & (cand <= chmin)
            any_set = setfreeze.any(axis=1)
            lamx = np.where(setfreeze[:, :, None] & (inc > 0),
                            cand[:, :, None], np.inf).min(axis=1)
            np.maximum(lamx, 0.0, out=lamx)
            fin = lamx < np.inf
            mfrz = fin | ~any_set[:, None]      # alpha-fill lanes w/o freezes
            lvl = np.where(fin, lamx, alpha)
            np.copyto(v, lvl, where=mfrz & (active > 0))
            active = active * ~mfrz
            if not active.any():
                break
    return v


def tree_feasible_batch(t: np.ndarray, mask: np.ndarray, edge_caps: np.ndarray,
                        region: FeasibleRegion, alpha: float,
                        chain: Optional[np.ndarray] = None,
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched ``lp.tree_feasible_at_time``: for each lane, is there a beta in
    ``region`` whose subtree sums respect every binding edge at time ``t``?

    Returns (feasible (P,), water-fill witness (P, d)).  An edge binds iff
    t*c < alpha - 1e-12 (otherwise interior re-encoding makes the edge
    unconstraining, paper Section V-B) — same threshold as the scalar oracle.
    ``chain`` optionally carries the precomputed subtree nesting relation
    (t-independent) across the bisection steps of one tree batch.
    """
    bounds = t[:, None] * edge_caps
    bnd = np.where(bounds < alpha - 1e-12, bounds, np.inf)
    wf = waterfill_batch(mask[:, 1:, :], bnd, alpha, chain=chain)
    xm, idxs = _region_consts(region)
    sig = np.cumsum(np.sort(wf, axis=-1), axis=-1)[..., idxs]
    return np.all(sig >= xm, axis=-1), wf


_REGION_CACHE: Dict[tuple, Tuple[np.ndarray, np.ndarray]] = {}


def _region_consts(region: FeasibleRegion) -> Tuple[np.ndarray, np.ndarray]:
    """(x - tol thresholds, cumsum gather indices) for the fast contains path
    (tol = 1e-9, the scalar oracle's default)."""
    key = (region.k, region.d, region.x)
    v = _REGION_CACHE.get(key)
    if v is None:
        v = (np.asarray(region.x, dtype=np.float64) - 1e-9,
             np.arange(region.d - region.k, region.d))
        _REGION_CACHE[key] = v
    return v


def _nest_of(inc: np.ndarray) -> np.ndarray:
    """Nesting relation of a laminar (P, S, d) family: sets overlap iff
    nested, so a boolean Gram matrix is the ancestor/descendant relation."""
    return np.matmul(inc, inc.transpose(0, 2, 1)) > 0


def _chain_of(mask: np.ndarray) -> np.ndarray:
    """Nesting relation of the subtree family encoded by full-tree masks."""
    return _nest_of(mask[:, 1:, :])


# ---------------------------------------------------------------------------
# Speculative bisection: evaluate a whole decision subtree per oracle call
# ---------------------------------------------------------------------------
#
# A bisection step's next midpoint depends on the current oracle answer, so
# naive batching still pays one oracle call per iteration.  Instead we
# evaluate ALL 2^L - 1 midpoints of the next L decision levels in one call
# and then walk the realized path arithmetically.  Midpoints are produced by
# the same 0.5*(lo+hi) recurrence the scalar bisection uses, so the realized
# path is bitwise identical; the discarded speculative rows never touch any
# state.  Oracle rows are cheap (vector width), oracle *calls* are not
# (Python dispatch), which makes this a ~L-fold cut of the hot-path latency.

def _spec_mids(lo: np.ndarray, hi: np.ndarray, levels: int) -> np.ndarray:
    """Breadth-first midpoints of the L-level bisection tree, (n, 2^L - 1)."""
    n = lo.shape[0]
    nn = (1 << levels) - 1
    los = np.empty((n, nn))
    his = np.empty((n, nn))
    mids = np.empty((n, nn))
    los[:, 0] = lo
    his[:, 0] = hi
    for i in range(nn):
        m = 0.5 * (los[:, i] + his[:, i])
        mids[:, i] = m
        lc, rc = 2 * i + 1, 2 * i + 2
        if lc < nn:
            los[:, lc] = los[:, i]
            his[:, lc] = m
        if rc < nn:
            los[:, rc] = m
            his[:, rc] = his[:, i]
    return mids


def _spec_walk(lo: np.ndarray, hi: np.ndarray, mids: np.ndarray,
               feas: np.ndarray, levels: int,
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Follow the realized path through the speculated tree: per level,
    feasible -> hi = mid (left child), infeasible -> lo = mid (right)."""
    n = lo.shape[0]
    ar = np.arange(n)
    node = np.zeros(n, dtype=np.int64)
    for _ in range(levels):
        m = mids[ar, node]
        fb = feas[ar, node]
        hi = np.where(fb, m, hi)
        lo = np.where(fb, lo, m)
        node = 2 * node + np.where(fb, 1, 2)
    return lo, hi


def _spec_walk_var(lo: np.ndarray, hi: np.ndarray, mids: np.ndarray,
                   feas: np.ndarray, lv: np.ndarray,
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-lane variable-depth ``_spec_walk`` (lane i walks lv[i] levels)."""
    n = lo.shape[0]
    ar = np.arange(n)
    node = np.zeros(n, dtype=np.int64)
    for step in range(_SPEC_LEVELS):
        on = step < lv
        m = mids[ar, node]
        fb = feas[ar, node]
        hi = np.where(on & fb, m, hi)
        lo = np.where(on & ~fb, m, lo)
        node = np.where(on, 2 * node + np.where(fb, 1, 2), node)
    return lo, hi


def tree_optimal_time_batch(mask: np.ndarray, edge_caps: np.ndarray,
                            region: FeasibleRegion, alpha: float,
                            iters: int = _EVAL_ITERS,
                            lanes: Optional[np.ndarray] = None) -> np.ndarray:
    """Batched ``lp.tree_optimal_time`` (time only): lockstep bisection with
    the water-fill oracle, matching the scalar hi-initialization and
    iteration count so results agree to bisection precision."""
    B = edge_caps.shape[0]
    if lanes is None:
        lanes = np.ones(B, dtype=bool)
    out = np.full(B, np.inf)
    idx = np.flatnonzero(lanes)
    if idx.size == 0:
        return out
    mask, edge_caps = mask[idx], edge_caps[idx]       # compact to live lanes
    chain = _chain_of(mask)
    valid = (edge_caps > 0).all(axis=1)
    safe = np.where(edge_caps > 0, edge_caps, 1.0)
    hi = np.where(valid, (alpha / safe).max(axis=1) * (1 + 1e-9) + 1e-12, np.inf)
    feas, _ = tree_feasible_batch(np.where(valid, hi, 1.0), mask, edge_caps,
                                  region, alpha, chain)
    feas &= valid
    need = valid & ~feas
    while need.any():
        hi = np.where(need, hi * 2.0, hi)
        over = hi >= 1e18
        f2, _ = tree_feasible_batch(np.where(need & ~over, hi, 1.0), mask,
                                    edge_caps, region, alpha, chain)
        feas |= need & ~over & f2
        need = valid & ~feas & ~over
    live = valid & feas
    lo = np.zeros(idx.size)
    lo, hi = _bisect_spec_lockstep(mask, edge_caps, region, alpha, chain,
                                   lo, hi, iters, live)
    out[idx] = np.where(live, hi, np.inf)
    return out


def _bisect_spec_lockstep(mask, edge_caps, region, alpha, chain, lo, hi,
                          iters: int, live: np.ndarray,
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Run ``iters`` bisection steps on fixed trees, _SPEC_LEVELS at a time."""
    n = lo.shape[0]
    done = 0
    mask_rep = ec_rep = ch_rep = None
    while done < iters:
        levels = min(_SPEC_LEVELS, iters - done)
        nn = (1 << levels) - 1
        if mask_rep is None or mask_rep.shape[0] != n * nn:
            mask_rep = np.repeat(mask, nn, axis=0)
            ec_rep = np.repeat(edge_caps, nn, axis=0)
            ch_rep = np.repeat(chain, nn, axis=0)
        mids = _spec_mids(lo, hi, levels)
        tq = np.where(live[:, None], mids, 1.0).reshape(-1)
        f, _ = tree_feasible_batch(tq, mask_rep, ec_rep, region, alpha, ch_rep)
        lo, hi = _spec_walk(lo, hi, mids, f.reshape(n, nn) | ~live[:, None],
                            levels)
        done += levels
    return lo, hi


def _refine_batch(mask: np.ndarray, edge_caps: np.ndarray,
                  region: FeasibleRegion, alpha: float, t_ub: np.ndarray,
                  lanes: np.ndarray, iters: int = _REFINE_ITERS) -> np.ndarray:
    """Batched ``ftr._refine``: bisect [0, t_ub] knowing t_ub is feasible.

    Lanes outside ``lanes`` keep their ``t_ub`` value; live lanes are
    compacted so sparse refinements (rare pivot accepts) stay cheap."""
    out = t_ub.copy()
    idx = np.flatnonzero(lanes)
    if idx.size == 0:
        return out
    mask, edge_caps = mask[idx], edge_caps[idx]
    chain = _chain_of(mask)
    lo = np.zeros(idx.size)
    hi = t_ub[idx].copy()
    live = np.ones(idx.size, dtype=bool)
    lo, hi = _bisect_spec_lockstep(mask, edge_caps, region, alpha, chain,
                                   lo, hi, iters, live)
    out[idx] = hi
    return out


# ---------------------------------------------------------------------------
# STAR / FR
# ---------------------------------------------------------------------------

def _star_parents(B: int, d: int) -> np.ndarray:
    return np.zeros((B, d + 1), dtype=np.int64)


def _star_time(flows: np.ndarray, direct: np.ndarray) -> np.ndarray:
    """max_i flows_i / c_i with inf on nonpositive links (scalar semantics)."""
    with np.errstate(divide="ignore", invalid="ignore"):
        t = np.where(direct > 0, flows / direct, np.inf)
    return t.max(axis=1)


def plan_star_batch(caps: np.ndarray, params: CodeParams) -> BatchPlanResult:
    """Conventional uniform-beta star regeneration over a batch."""
    B = caps.shape[0]
    d = params.d
    direct = caps[:, 1:, 0]
    f = min(params.beta, params.alpha)
    flows = np.full((B, d), f)
    return BatchPlanResult("star", _star_time(flows, direct), flows.sum(axis=1),
                           np.full((B, d), params.beta), _star_parents(B, d))


def minmax_time_star_batch(direct: np.ndarray, region: FeasibleRegion,
                           alpha: float,
                           lanes: Optional[np.ndarray] = None) -> np.ndarray:
    """Batched ``lp.minmax_time_star``: exact optimum of problem (1) via
    lockstep bisection on the coordinate-wise max point (Theorem 1 makes the
    max-point test exact for fixed t)."""
    B = direct.shape[0]
    if lanes is None:
        lanes = np.ones(B, dtype=bool)

    def feas(t: np.ndarray) -> np.ndarray:
        beta_hat = np.minimum(t[:, None] * direct, alpha)
        return region.contains_batch(beta_hat, tol=1e-12)

    hi = np.ones(B)
    ok = feas(hi) | ~lanes
    while not ok.all():
        hi = np.where(ok, hi, hi * 2.0)
        ok |= (hi > 1e18) | feas(hi)
    dead = lanes & (hi > 1e18)
    lo = np.zeros(B)
    for _ in range(_STAR_ITERS):
        mid = 0.5 * (lo + hi)
        f = feas(mid)
        hi = np.where(f, mid, hi)
        lo = np.where(f, lo, mid)
    return np.where(dead, np.inf, hi)


def _fr_msr_closed_form_batch(direct: np.ndarray, params: CodeParams,
                              ) -> Tuple[np.ndarray, np.ndarray]:
    """Vectorized ``star.fr_closed_form_msr``: (betas, optimal time)."""
    d, k, M = params.d, params.k, params.M
    m = d - k + 1
    order = np.argsort(direct, axis=1, kind="stable")
    csort = np.take_along_axis(direct, order, axis=1)
    # left to right, as the scalar oracle sums (ndarray.sum goes pairwise
    # from m >= 8 on)
    denom = np.zeros(direct.shape[0])
    for i in range(m):
        denom = denom + csort[:, i]
    rank = np.arange(d)[None, :]
    beta_sorted = np.where(rank < m, csort, csort[:, m - 1:m]) * M / (k * denom[:, None])
    betas = np.empty_like(beta_sorted)
    np.put_along_axis(betas, order, beta_sorted, axis=1)
    time = (betas / direct).max(axis=1)
    return betas, time


def plan_fr_batch(caps: np.ndarray, params: CodeParams,
                  region: Optional[FeasibleRegion] = None,
                  minimize_traffic: bool = True,
                  witness: str = "exact",
                  profile=None) -> BatchPlanResult:
    """Flexible Regeneration over a batch (paper problem (1)).

    Closed form at MSR; elsewhere the batched bisection finds the optimal
    time and the exact level-cut oracle extracts all trials' traffic-
    minimal witnesses in one array program (``witness="lp"`` restores the
    per-trial HiGHS loop, kept as the correctness oracle).  ``profile``
    (optional, see :func:`_pstage`) times the ``closed_form`` /
    ``star_bisection`` / ``witness`` stages."""
    if witness not in ("exact", "lp"):
        raise ValueError(f"unknown witness engine {witness!r}")
    B = caps.shape[0]
    d = params.d
    direct = caps[:, 1:, 0]
    if region is None:
        region = msr_region(params) if params.is_msr else heuristic_region(params)

    betas = np.zeros((B, d))
    lb = np.zeros(B)
    closed = np.zeros(B, dtype=bool)
    if params.is_msr:
        closed = (direct > 0).all(axis=1)
        if closed.any():
            with _pstage(profile, "closed_form"):
                cb, ct = _fr_msr_closed_form_batch(
                    np.where(closed[:, None], direct, 1.0), params)
            betas = np.where(closed[:, None], cb, betas)
            lb = np.where(closed, ct, lb)
    rest = ~closed
    if rest.any():
        with _pstage(profile, "star_bisection"):
            t_rest = minmax_time_star_batch(direct, region, params.alpha,
                                            lanes=rest)
        if profile is not None:
            profile.count("bisection_iters", _STAR_ITERS)
        lb = np.where(rest, t_rest, lb)
        if minimize_traffic and witness == "exact":
            with _pstage(profile, "witness"):
                wb = wit.min_traffic_batch(t_rest, direct, region,
                                           params.alpha, lanes=rest)
            betas = np.where((rest & np.isfinite(t_rest))[:, None], wb, betas)
        else:
            with _pstage(profile, "witness"):
                for b in np.flatnonzero(rest):
                    if not np.isfinite(t_rest[b]):
                        continue
                    if minimize_traffic:
                        betas[b] = lp.min_traffic_at_time(
                            float(t_rest[b]), direct[b].tolist(), region,
                            params.alpha, witness="lp")
                    else:
                        betas[b] = np.minimum(t_rest[b] * direct[b],
                                              params.alpha)
    if profile is not None:
        profile.count("lanes", B)
        profile.count("closed_form_lanes", int(closed.sum()))
        profile.count("bisection_lanes", int(rest.sum()))

    flows = np.minimum(betas, params.alpha)
    times = np.maximum(_star_time(flows, direct), 0.0) if d else np.zeros(B)
    # lanes whose star problem is infeasible (zero-capacity uplinks): report
    # inf traffic too, matching plan_ftr_batch's convention, so means poison
    # instead of counting a failed repair as free
    bad = ~np.isfinite(lb)
    times = np.where(bad, np.inf, times)
    traffic = np.where(bad, np.inf, flows.sum(axis=1))
    return BatchPlanResult("fr", times, traffic, betas,
                           _star_parents(B, d), lower_bounds=lb)


def plan_shah_batch(caps: np.ndarray, params: CodeParams,
                    beta_max: Optional[float] = None) -> BatchPlanResult:
    """Batched Shah et al. [6] baseline: beta_i in [0, beta_max],
    sum beta_i >= gamma, greedy water-filling from the fastest links.

    Mirrors ``star.plan_shah`` decision for decision — the same hi-doubling
    and 60-step bisection on ``sum_i min(t*c_i, beta_max) >= gamma``, the
    same ascending-capacity surplus trim — with every reduction written as a
    *sequential* d-step accumulation so each lane reproduces the scalar
    planner's left-to-right float arithmetic bit for bit (d is small; the
    batch axis is where the vector width comes from).
    """
    from .regions import shah_region_thresholds

    B = caps.shape[0]
    d = params.d
    direct = caps[:, 1:, 0]
    if beta_max is None:
        beta_max = params.alpha  # most permissive per-provider cap
    gamma = shah_region_thresholds(params, beta_max)
    bidx = np.arange(B)

    def tot(t: np.ndarray) -> np.ndarray:
        """sum_i min(t * c_i, beta_max), accumulated left to right."""
        acc = np.zeros(B)
        for i in range(d):
            acc = acc + np.minimum(t * direct[:, i], beta_max)
        return acc

    # bisection on t: achievable iff tot(t) >= gamma (scalar hi-doubling
    # gives up past 1e18 and reports an infeasible lane)
    lo = np.zeros(B)
    hi = np.ones(B)
    dead = np.zeros(B, dtype=bool)
    need = tot(hi) < gamma
    while (need & ~dead).any():
        grow = need & ~dead
        hi = np.where(grow, hi * 2.0, hi)
        dead |= grow & (hi > 1e18)
        need = tot(hi) < gamma
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        f = tot(mid) >= gamma
        hi = np.where(f, mid, hi)
        lo = np.where(f, lo, mid)
    t = hi
    betas = np.minimum(t[:, None] * direct, beta_max)
    # trim surplus from the slowest contributors (they set the clock);
    # stable ascending-capacity order == the scalar planner's sorted() scan
    acc = np.zeros(B)
    for i in range(d):
        acc = acc + betas[:, i]
    surplus = acc - gamma
    order = np.argsort(direct, axis=1, kind="stable")
    for j in range(d):
        i = order[:, j]
        bi = betas[bidx, i]
        cut = np.minimum(np.maximum(surplus, 0.0), bi)
        betas[bidx, i] = bi - cut
        surplus = surplus - cut
    # infeasible lanes match the scalar contract: zero betas, inf time,
    # zero traffic (no flows were ever assigned)
    betas = np.where(dead[:, None], 0.0, betas)
    flows = np.minimum(betas, params.alpha)
    times = np.where(dead, np.inf, _star_time(flows, direct))
    return BatchPlanResult("shah", times, flows.sum(axis=1), betas,
                           _star_parents(B, d))


# ---------------------------------------------------------------------------
# TR — Algorithm 1, incremental and batched
# ---------------------------------------------------------------------------

def plan_tr_batch(caps: np.ndarray, params: CodeParams) -> BatchPlanResult:
    """Batched Algorithm 1 (greedy regeneration tree, uniform traffic).

    Instead of recomputing Theorem-3 flows for every candidate edge, the
    engine keeps per-node subtree sizes and ancestor masks: a candidate
    (v -> u) changes flows only on the path u -> root, so its partial-tree
    time is  max(beta/c(v,u), max over edges of the path-adjusted times) —
    all evaluated as one (B, d+1, d+1) tensor per greedy step."""
    B = caps.shape[0]
    d = params.d
    D1 = d + 1
    b = params.beta
    alpha = params.alpha
    bidx = np.arange(B)

    parent = np.zeros((B, D1), dtype=np.int64)
    attached = np.zeros((B, D1), dtype=bool)
    attached[:, 0] = True
    anc = np.zeros((B, D1, D1), dtype=bool)   # anc[b, w, x]: w ancestor-or-self of x
    size = np.zeros((B, D1))                  # providers in subtree (attached)
    edge_c = np.zeros((B, D1))                # c(u, parent(u)) for attached u >= 1

    new_flow = min(b, alpha)
    with np.errstate(divide="ignore", invalid="ignore"):
        new_edge_t = np.where(caps > 0, new_flow / caps, np.inf)  # (B, v, u)

    for _ in range(d):
        att_e = attached.copy()
        att_e[:, 0] = False
        f_now = np.minimum(size * b, alpha)
        f_inc = np.minimum((size + 1.0) * b, alpha)
        with np.errstate(divide="ignore", invalid="ignore"):
            h = np.where(att_e, np.where(edge_c > 0, f_now / edge_c, np.inf), -np.inf)
            g = np.where(att_e, np.where(edge_c > 0, f_inc / edge_c, np.inf), -np.inf)
        # T_path[u]: partial-tree time if the new provider attaches under u
        val = np.where(anc, g[:, :, None], h[:, :, None])       # (B, w, u)
        T_path = np.maximum(val.max(axis=1), 0.0)               # (B, u)
        cand_t = np.maximum(new_edge_t, T_path[:, None, :])     # (B, v, u)
        valid = (~attached)[:, :, None] & attached[:, None, :]
        cand_t = np.where(valid, cand_t, np.inf)
        # selection: lexicographic min over (t, -c(v,u), v, u) — identical to
        # the scalar greedy's strict tuple comparison in iteration order
        tmin = cand_t.min(axis=(1, 2))
        is_t = valid & (cand_t == tmin[:, None, None])
        cgrid = np.where(is_t, caps, -np.inf)
        cmax = cgrid.max(axis=(1, 2))
        sel = is_t & (cgrid == cmax[:, None, None])
        choice = sel.reshape(B, -1).argmax(axis=1)
        v_sel = choice // D1
        u_sel = choice % D1
        # incremental state update
        parent[bidx, v_sel] = u_sel
        attached[bidx, v_sel] = True
        edge_c[bidx, v_sel] = caps[bidx, v_sel, u_sel]
        size += anc[bidx, :, u_sel]
        size[bidx, v_sel] = 1.0
        anc[bidx, :, v_sel] = anc[bidx, :, u_sel]
        anc[bidx, v_sel, v_sel] = True

    flows = np.minimum(size[:, 1:] * b, alpha)
    with np.errstate(divide="ignore", invalid="ignore"):
        et = np.where(edge_c[:, 1:] > 0, flows / edge_c[:, 1:], np.inf)
    return BatchPlanResult("tr", et.max(axis=1), flows.sum(axis=1),
                           np.full((B, d), b), parent)


# ---------------------------------------------------------------------------
# FTR — Algorithm 2, batched candidate population
# ---------------------------------------------------------------------------

def _ftr_candidates(caps: np.ndarray, params: CodeParams,
                    tr_parents: np.ndarray) -> np.ndarray:
    """All Algorithm-2 initial trees (core sizes i = 0..d) plus the TR tree.

    The max-capacity core greedy is deterministic, so the core of size i is a
    prefix of the core of size d: one growth pass per trial yields every
    candidate.  Returns (B, d+2, d+1) parent arrays."""
    B, D1, _ = caps.shape
    d = D1 - 1
    bidx = np.arange(B)
    in_core = np.zeros((B, D1), dtype=bool)
    in_core[:, 0] = True
    core_pos = np.full((B, D1), D1 + 1, dtype=np.int64)
    core_pos[:, 0] = 0
    parfull = np.zeros((B, D1), dtype=np.int64)
    for step in range(d):
        cuv = np.where(~in_core[:, :, None] & in_core[:, None, :], caps, -np.inf)
        cuv[:, 0, :] = -np.inf
        rowbest = cuv.max(axis=2)
        u_sel = rowbest.argmax(axis=1)
        best = rowbest[bidx, u_sel]
        # attach point: first core node (in core order) achieving the max —
        # the scalar greedy's strict-> scan order
        pos = np.where(cuv[bidx, u_sel, :] == best[:, None], core_pos, D1 + 2)
        v_sel = pos.argmin(axis=1)
        parfull[bidx, u_sel] = v_sel
        in_core[bidx, u_sel] = True
        core_pos[bidx, u_sel] = step + 1

    cands = np.zeros((B, d + 2, D1), dtype=np.int64)
    for i in range(d + 1):
        mask_core = core_pos <= i
        cu = np.where(mask_core[:, None, :], caps, -np.inf)
        m = cu.max(axis=2)
        posg = np.where(cu == m[:, :, None], core_pos[:, None, :], D1 + 2)
        vbest = posg.argmin(axis=2)
        par = np.where(mask_core, parfull, vbest)
        par[:, 0] = 0
        cands[:, i] = par
    cands[:, d + 1] = tr_parents
    return cands


def _candidate_stage(caps: np.ndarray, cands: np.ndarray, dup: np.ndarray,
                     region: FeasibleRegion, alpha: float) -> np.ndarray:
    """Evaluate every candidate tree with the scalar planner's incumbent
    pruning, asynchronously across lanes.

    Each lane walks its own (probe at incumbent -> refine on accept -> next
    candidate) sequence — the exact per-network oracle-query sequence of
    ``ftr.plan_ftr`` — but every global step gathers all lanes' pending
    queries into ONE batched feasibility call.  Lanes refining candidate 3
    share oracle calls with lanes still probing candidate 7, so wall time is
    the *maximum* per-lane query count instead of the lockstep sum over
    candidates.  Returns the (B, C) per-candidate optimal times (inf for
    pruned/duplicate candidates).
    """
    B, C, D1 = cands.shape
    d = D1 - 1
    flat = cands.reshape(B * C, D1)
    mask_all = subtree_masks(flat)                      # (B*C, D1, d)
    lane_of = np.repeat(np.arange(B), C)
    uu = np.arange(1, D1)[None, :]
    ec_all = caps[lane_of[:, None], uu, flat[:, 1:]]    # (B*C, d)
    inc_all = np.ascontiguousarray(mask_all[:, 1:, :])
    chain_all = _nest_of(inc_all)
    ec_ok = (ec_all > 0).all(axis=1).reshape(B, C)
    hi0_all = ((alpha / np.where(ec_all > 0, ec_all, 1.0)).max(axis=1)
               * (1 + 1e-9) + 1e-12).reshape(B, C)

    START, DOUBLE, BISECT, PROBE, DONE = range(5)
    bidx = np.arange(B)
    c_cur = np.zeros(B, dtype=np.int64)
    mode = np.full(B, DONE)
    lo = np.zeros(B)
    hi = np.zeros(B)
    left = np.zeros(B, dtype=np.int64)
    t_cand = np.full((B, C), np.inf)
    incumbent = np.full(B, np.inf)

    def enter(lanes: np.ndarray) -> None:
        """Route lanes into their current candidate's first query (skipping
        duplicates, exhausted lanes, and zero-capacity trees)."""
        lanes = lanes.copy()
        while lanes.any():
            done = lanes & (c_cur >= C)
            mode[done] = DONE
            lanes &= ~done
            if not lanes.any():
                break
            cc = np.minimum(c_cur, C - 1)
            isdup = lanes & dup[bidx, cc]
            if isdup.any():
                c_cur[isdup] += 1
                continue
            fin = lanes & np.isfinite(incumbent)
            mode[fin] = PROBE
            lanes &= ~fin
            if not lanes.any():
                break
            bad = lanes & ~ec_ok[bidx, cc]      # scalar: inf with no oracle call
            good = lanes & ~bad
            hi[good] = hi0_all[bidx, cc][good]
            mode[good] = START
            lanes = bad
            c_cur[bad] += 1
        return

    enter(np.ones(B, dtype=bool))
    while True:
        act = mode != DONE
        if not act.any():
            break
        mode0 = mode.copy()
        # single-point queries (probe / initial-hi / doubling) ride in the
        # same oracle call as the speculated bisection trees of refining lanes
        oidx = np.flatnonzero(act & (mode0 != BISECT))
        bidx_b = np.flatnonzero(act & (mode0 == BISECT))
        t_single = np.where(mode0 == PROBE, incumbent, hi)[oidx]
        sel = np.concatenate([oidx * C + c_cur[oidx],
                              np.repeat(bidx_b * C + c_cur[bidx_b], _SPEC_NN)])
        mids = _spec_mids(lo[bidx_b], hi[bidx_b], _SPEC_LEVELS)
        f_s, _ = tree_feasible_batch(
            np.concatenate([t_single, mids.reshape(-1)]), mask_all[sel],
            ec_all[sel], region, alpha, chain_all[sel])
        f = np.zeros(B, dtype=bool)
        f[oidx] = f_s[:oidx.size]
        f_spec = f_s[oidx.size:].reshape(-1, _SPEC_NN)
        adv = np.zeros(B, dtype=bool)
        # probe at the incumbent: accept -> refine [0, incumbent], else prune
        pr = act & (mode0 == PROBE)
        acc = pr & f
        mode[acc] = BISECT
        lo[acc] = 0.0
        hi[acc] = incumbent[acc]
        left[acc] = _REFINE_ITERS
        adv |= pr & ~f
        # fresh solve: initial hi check, then bisect (or double on failure)
        st = act & (mode0 == START)
        stf = st & f
        mode[stf] = BISECT
        lo[stf] = 0.0
        left[stf] = _EVAL_ITERS
        std = st & ~f
        hi[std] *= 2.0
        mode[std] = DOUBLE
        db = act & (mode0 == DOUBLE)
        dbf = db & f
        mode[dbf] = BISECT
        lo[dbf] = 0.0
        left[dbf] = _EVAL_ITERS
        dbx = db & ~f
        over = dbx & (hi >= 1e18)
        adv |= over
        hi[dbx & ~over] *= 2.0
        # bisection steps (shared by refine and fresh solve), L levels at once
        if bidx_b.size:
            lv = np.minimum(left[bidx_b], _SPEC_LEVELS)
            lo_b, hi_b = _spec_walk_var(lo[bidx_b], hi[bidx_b], mids, f_spec,
                                        lv)
            lo[bidx_b] = lo_b
            hi[bidx_b] = hi_b
            left[bidx_b] -= lv
        fin_b = act & (mode0 == BISECT) & (left == 0)
        if fin_b.any():
            t_cand[bidx[fin_b], c_cur[fin_b]] = hi[fin_b]
            incumbent[fin_b] = np.minimum(incumbent[fin_b], hi[fin_b])
            adv |= fin_b
        c_cur[adv] += 1
        enter(adv)
    return t_cand


def _local_search_batch(caps: np.ndarray, parents: np.ndarray,
                        t_cur: np.ndarray, region: FeasibleRegion,
                        alpha: float, alive: np.ndarray,
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched ``ftr._local_search``: the same pivot sweep (u ascending, up to
    8 alternative parents in decreasing capacity) with per-lane asynchronous
    progress.

    Each lane follows the scalar planner's exact query sequence — probe the
    alternatives of node u in order under the incumbent time, bisect [0, t]
    on the first accept, replay the remaining alternatives on the updated
    tree — but lanes advance independently: every global step gathers one
    stacked probe wave (all untried alternatives of a lane's current node)
    plus the pending bisection midpoints of refining lanes into a single
    batched oracle call.  Probes cost one oracle *row*, not one call, and a
    lane's 28-step refinement overlaps other lanes' scanning.
    """
    L, D1 = parents.shape
    d = D1 - 1
    WANT, REF, SEQ, DONE = range(4)
    mode = np.where(alive, WANT, DONE)
    u = np.ones(L, dtype=np.int64)
    rnd = np.zeros(L, dtype=np.int64)
    jj = np.zeros(L, dtype=np.int64)
    resume = np.zeros(L, dtype=np.int64)
    improved = np.zeros(L, dtype=bool)
    cur_p = np.zeros(L, dtype=np.int64)
    nok = np.zeros(L, dtype=np.int64)
    alts = np.zeros((L, _MAX_ALTS), dtype=np.int64)
    lo = np.zeros(L)
    hi = np.zeros(L)
    left = np.zeros(L, dtype=np.int64)
    # per-lane arrays of the CURRENT tree, updated on pivot accepts; probe
    # trees differ by one edge, so their masks derive incrementally from these
    bm, bec = _tree_arrays(caps, parents)
    binc = np.ascontiguousarray(bm[:, 1:, :])
    bch = _nest_of(binc)
    root_onehot = np.zeros(D1)
    root_onehot[0] = 1.0

    def advance(lanes: np.ndarray) -> None:
        """Scalar loop bookkeeping: next node, next round, or stop."""
        u[lanes] += 1
        over = lanes & (u > d)
        stop = over & (~improved | (rnd >= _MAX_ROUNDS - 1))
        mode[stop] = DONE
        cont = over & ~stop
        rnd[cont] += 1
        u[cont] = 1
        improved[cont] = False
        mode[lanes & ~stop] = WANT

    def enter_refine(lanes: np.ndarray, rows: np.ndarray, qmask, qec, qch,
                     v_new: np.ndarray, next_j: np.ndarray) -> None:
        """Accept pivot v_new on ``lanes``: adopt the edge, cache the probed
        tree's arrays (they are the refine tree's), bisect [0, t_cur]."""
        li = np.flatnonzero(lanes)
        parents[li, u[li]] = v_new[li]
        cur_p[li] = v_new[li]
        bm[li] = qmask[rows[li]]
        bec[li] = qec[rows[li]]
        bch[li] = qch[rows[li]]
        resume[li] = next_j[li]
        mode[li] = REF
        lo[li] = 0.0
        hi[li] = t_cur[li]
        left[li] = _REFINE_ITERS
        improved[li] = True

    while True:
        if not (mode != DONE).any():
            break
        # -- compute alternative lists for lanes arriving at a new node -----
        fresh = mode == WANT
        while fresh.any():
            iw = np.flatnonzero(fresh)
            aw = np.arange(iw.size)
            desc = bm[iw, u[iw], :] > 0
            cpu = caps[iw, u[iw], :]
            okw = cpu > 0
            okw[aw, u[iw]] = False
            pw = parents[iw, u[iw]]
            okw[aw, pw] = False
            okw[:, 1:] &= ~desc
            nokw = np.minimum(okw.sum(axis=1), _MAX_ALTS)
            ordw = np.argsort(np.where(okw, -cpu, np.inf), axis=1,
                              kind="stable")[:, :_MAX_ALTS]
            nok[iw] = nokw
            alts[iw, :ordw.shape[1]] = ordw   # D1 may be below _MAX_ALTS
            cur_p[iw] = pw
            jj[iw] = 0
            empty = np.zeros(L, dtype=bool)
            empty[iw] = nokw == 0
            fresh = empty
            if empty.any():
                advance(empty)           # skip nodes with no alternatives
                fresh = empty & (mode == WANT)
        if not (mode != DONE).any():
            break
        # -- assemble one batched oracle call over all pending queries ------
        sidx = np.flatnonzero(mode == SEQ)
        widx = np.flatnonzero(mode == WANT)
        ridx = np.flatnonzero(mode == REF)
        reps = nok[widx]
        wlane = np.repeat(widx, reps)
        csum = np.cumsum(reps) - reps
        wj = np.arange(int(reps.sum())) - np.repeat(csum, reps)
        plane = np.concatenate([sidx, wlane])
        palt = np.concatenate([alts[sidx, jj[sidx]], alts[wlane, wj]])
        # moving edge u -> palt changes only the chains of u's descendants:
        # inside subtree(u) they keep their base ancestors, above it they
        # adopt the ancestor-or-self chain of the new parent
        R = plane.size
        ar = np.arange(R)
        base_m = bm[plane]
        urow = u[plane]
        dsc = base_m[ar, urow, :]                         # (R, d) 0/1
        in_sub = np.zeros((R, D1))
        in_sub[:, 1:] = dsc
        anc_v = np.where((palt >= 1)[:, None],
                         base_m[ar[:, None], np.arange(D1)[None, :],
                                np.maximum(palt - 1, 0)[:, None]],
                         root_onehot[None, :])
        pmask = np.where(dsc[:, None, :] > 0,
                         np.minimum(base_m * in_sub[:, :, None]
                                    + anc_v[:, :, None], 1.0),
                         base_m)
        pec = bec[plane].copy()
        pec[ar, urow - 1] = caps[plane, urow, palt]
        pinc = np.ascontiguousarray(pmask[:, 1:, :])
        pch = _nest_of(pinc)
        rrep = np.repeat(ridx, _SPEC_NN)
        mids = _spec_mids(lo[ridx], hi[ridx], _SPEC_LEVELS)
        qmask = np.concatenate([pmask, bm[rrep]])
        qec = np.concatenate([pec, bec[rrep]])
        qch = np.concatenate([pch, bch[rrep]])
        qt = np.concatenate([(t_cur * _PROBE_SLACK)[plane], mids.reshape(-1)])
        f, _ = tree_feasible_batch(qt, qmask, qec, region, alpha, qch)
        nS, nW = sidx.size, wlane.size
        # -- refining lanes: L speculated bisection levels -----------------
        if ridx.size:
            fR = f[nS + nW:].reshape(-1, _SPEC_NN)
            lv = np.minimum(left[ridx], _SPEC_LEVELS)
            lo_r, hi_r = _spec_walk_var(lo[ridx], hi[ridx], mids, fR, lv)
            lo[ridx] = lo_r
            hi[ridx] = hi_r
            left[ridx] -= lv
            fin = ridx[left[ridx] == 0]
            if fin.size:
                t_cur[fin] = hi[fin]
                jj[fin] = resume[fin]
                more = np.zeros(L, dtype=bool)
                more[fin] = jj[fin] < nok[fin]
                mode[fin] = SEQ           # overwritten below if exhausted
                spent = np.zeros(L, dtype=bool)
                spent[fin] = ~more[fin]
                advance(spent)
        # -- sequential replay probes (post-accept alternatives) -----------
        if nS:
            accS = np.zeros(L, dtype=bool)
            accS[sidx] = f[:nS]
            rowS = np.zeros(L, dtype=np.int64)
            rowS[sidx] = np.arange(nS)
            if accS.any():
                enter_refine(accS, rowS, qmask, qec, qch,
                             alts[np.arange(L), np.minimum(jj, _MAX_ALTS - 1)],
                             jj + 1)
            rejS = np.zeros(L, dtype=bool)
            rejS[sidx] = ~f[:nS]
            jj[rejS] += 1
            outS = rejS & (jj >= nok)
            advance(outS)
        # -- stacked probe waves: first feasible alternative wins ----------
        if nW:
            fW = f[nS:nS + nW]
            firstj = np.full(L, _MAX_ALTS + 1, dtype=np.int64)
            np.minimum.at(firstj, wlane[fW], wj[fW])
            accW = np.zeros(L, dtype=bool)
            accW[widx] = firstj[widx] <= _MAX_ALTS
            if accW.any():
                rowW = np.zeros(L, dtype=np.int64)
                rowW[widx] = nS + csum[np.arange(widx.size)]
                rowW += np.minimum(firstj, _MAX_ALTS)
                enter_refine(accW, rowW, qmask, qec, qch,
                             alts[np.arange(L),
                                  np.minimum(firstj, _MAX_ALTS - 1)],
                             firstj + 1)
            noW = np.zeros(L, dtype=bool)
            noW[widx] = firstj[widx] > _MAX_ALTS
            advance(noW)
    return parents, t_cur


def plan_ftr_batch(caps: np.ndarray, params: CodeParams,
                   region: Optional[FeasibleRegion] = None,
                   local_search: bool = True,
                   witness: str = "exact",
                   profile=None) -> BatchPlanResult:
    """Batched Algorithm 2 + pivot search + final high-precision solve.

    Mirrors ``ftr.plan_ftr`` decision for decision: candidate 0 is solved by
    full bisection; later candidates are probed once at the per-trial
    incumbent and refined only when they can beat it; the three best trees
    enter the pivot search; the winner gets a 50-iteration solve and one
    batched level cut of the water-fill witness for the traffic-minimal
    betas (``witness="lp"`` restores the per-trial HiGHS loop).
    ``profile`` (optional, see :func:`_pstage`) times the ``tr_seed`` /
    ``candidates`` / ``local_search`` / ``final_solve`` / ``witness``
    stages."""
    if witness not in ("exact", "lp"):
        raise ValueError(f"unknown witness engine {witness!r}")
    B, D1, _ = caps.shape
    d = D1 - 1
    if region is None:
        region = msr_region(params) if params.is_msr else heuristic_region(params)
    alpha = params.alpha
    bidx = np.arange(B)

    with _pstage(profile, "tr_seed"):
        tr = plan_tr_batch(caps, params)
    with _pstage(profile, "candidates"):
        cands = _ftr_candidates(caps, params, tr.parents)
        C = cands.shape[1]
        # duplicate trees are skipped exactly as the scalar planner's
        # seen-set
        eq = (cands[:, :, None, :] == cands[:, None, :, :]).all(axis=-1)
        dup = (eq & np.tril(np.ones((C, C), dtype=bool), -1)[None]
               ).any(axis=2)
        t_cand = _candidate_stage(caps, cands, dup, region, alpha)

    order = np.argsort(t_cand, axis=1, kind="stable")
    best_t = t_cand[bidx, order[:, 0]]
    best_par = cands[bidx, order[:, 0]].copy()

    if local_search:
        with _pstage(profile, "local_search"):
            top = order[:, :3]
            par_ls = cands[bidx[:, None], top].reshape(B * 3, D1).copy()
            t_ls = np.take_along_axis(t_cand, top, axis=1).reshape(B * 3)
            caps_ls = np.repeat(caps, 3, axis=0)
            par_ls, t_ls = _local_search_batch(caps_ls, par_ls, t_ls, region,
                                               alpha, np.isfinite(t_ls))
            par_ls = par_ls.reshape(B, 3, D1)
            t_ls = t_ls.reshape(B, 3)
            for s in range(3):
                upd = t_ls[:, s] < best_t
                best_t = np.where(upd, t_ls[:, s], best_t)
                best_par = np.where(upd[:, None], par_ls[:, s], best_par)

    # final high-precision solve on the winning tree + traffic-minimal
    # witness: one batched level cut of the water-fill point (exact), or the
    # legacy per-trial HiGHS loop under witness="lp"
    with _pstage(profile, "final_solve"):
        mask, ec = _tree_arrays(caps, best_par)
        solvable = np.isfinite(best_t)
        t_star = tree_optimal_time_batch(mask, ec, region, alpha,
                                         iters=_FINAL_ITERS, lanes=solvable)
        _, wf = tree_feasible_batch(np.where(solvable, t_star, 1.0), mask,
                                    ec, region, alpha)
    with _pstage(profile, "witness"):
        if witness == "exact":
            betas = np.where(solvable[:, None],
                             wit.level_cut_batch(wf, region, lanes=solvable),
                             0.0)
        else:
            betas = np.where(solvable[:, None], wf, 0.0)
            for b in np.flatnonzero(solvable):
                parent_dict = {u: int(best_par[b, u]) for u in range(1, D1)}
                cap_of_edge = {(u, p): float(caps[b, u, p])
                               for u, p in parent_dict.items()}
                w = lp.tree_feasible_at_time(float(t_star[b]), parent_dict,
                                             cap_of_edge, region, alpha,
                                             minimize_traffic=True,
                                             witness="lp")
                if w is not None:
                    betas[b] = w
    if profile is not None:
        profile.count("lanes", B)
        profile.count("candidate_trees", int(C))
        profile.count("final_solve_iters", _FINAL_ITERS)

    sub = np.einsum("bud,bd->bu", mask[:, 1:, :], betas)
    flows = np.minimum(sub, alpha)
    with np.errstate(divide="ignore", invalid="ignore"):
        et = np.where(ec > 0, flows / ec, np.inf)
    times = np.where(solvable, et.max(axis=1), np.inf)
    traffic = np.where(solvable, flows.sum(axis=1), np.inf)
    return BatchPlanResult("ftr", times, traffic, betas, best_par,
                           lower_bounds=t_star)


def plans_from_batch(res: BatchPlanResult, params: CodeParams,
                     ) -> "list[RepairPlan]":
    """Materialize one scalar :class:`RepairPlan` per batch lane.

    The flows are re-derived from (parents, betas) via ``tree_flows`` —
    the same map every scalar planner uses — so downstream consumers
    (``RepairPlan.validate``, the RLNC data-plane executor, the fleet
    simulator's link-sharing model) see exactly the structure the batched
    engine planned.  Infeasible lanes (``times[b] = inf``) keep their inf
    time so callers can filter or poison means, matching the batch
    convention.

    A scalar-fallback batch (``res.plans`` set by ``api.plan_many``) returns
    the original scalar plans verbatim: rctree's fixed-beta-per-edge flows,
    for example, are *not* the ``tree_flows`` of (parents, betas) and must
    survive the round trip untouched."""
    from .params import RepairPlan, tree_flows

    if res.plans is not None:
        return list(res.plans)
    B = res.times.shape[0]
    plans = []
    for b in range(B):
        parent = {u: int(res.parents[b, u])
                  for u in range(1, res.parents.shape[1])}
        betas = [float(x) for x in res.betas[b]]
        flows = tree_flows(parent, betas, params.alpha)
        lb = (float(res.lower_bounds[b])
              if res.lower_bounds is not None else None)
        plans.append(RepairPlan(res.scheme, params, parent, betas, flows,
                                float(res.times[b]), lower_bound=lb))
    return plans


def plan_batch(caps: np.ndarray, params: CodeParams, scheme: str,
               **kwargs) -> BatchPlanResult:
    """Deprecated shim: plan one scheme across a batch of networks.

    Use :func:`repro.core.api.plan_many` — the registry-backed dispatcher —
    instead.  This shim resolves the scheme through the registry and now
    forwards per-scheme planner kwargs (e.g. ``witness="lp"`` for fr/ftr),
    which the old hand-rolled dispatch silently could not.  Schemes
    registered with ``batched=None`` (e.g. rctree) keep raising ValueError,
    matching the historical contract; ``plan_many(engine="batched")`` warns
    and falls back to the scalar planner instead.
    """
    from . import api

    api.warn_deprecated("plan_batch", "plan_many()")
    spec = api.get_scheme(scheme)
    if spec.batched is None:
        raise ValueError(
            f"no batched planner for scheme {scheme!r}; "
            f"available: {sorted(api.scheme_names(batched=True))}")
    return spec.batched(caps, params, **kwargs)
