"""Jit-compiled JAX backend for the batched planning engine (ROADMAP item 2).

The NumPy batched engine (``repro.core.batched``) advances all Monte-Carlo
lanes in lockstep but still pays Python dispatch per oracle call — ~240
``waterfill_batch`` invocations per FTR batch, each a handful of small
ufuncs.  This module re-expresses the same planners as jit-compiled JAX
programs so the *entire* plan — star bisection, Theorem-1 feasibility
(sort + cumsum), the water-fill oracle, FTR's candidate stage and pivot
local search, and the level-cut min-traffic witness — compiles to one XLA
executable per (batch, d, k) shape:

* every bisection runs a fixed trip count (``lax.fori_loop`` with per-lane
  iteration budgets masked in, ``lax.while_loop`` only where the NumPy
  engine also loops data-dependently: hi-doubling, water-fill rounds,
  probe waves);
* per-lane Python state (the NumPy engine's mode machines) becomes masked
  lanes: every lane issues every oracle query, with ``jnp.where`` keeping
  non-participating lanes at a benign t=1.0 probe whose answer is ignored;
* float64 is enabled via the scoped ``jax.enable_x64`` context around
  each planner call (never the global flag, so importing this module
  cannot perturb float32-default JAX code elsewhere in the process).  The
  TPU has no float64 units; XLA emulates the float64 ops there.

The NumPy planners remain the oracle: decision sequences (incumbent
pruning, duplicate skips, pivot accept order, tie-breaks) are replicated
operation for operation, so jax plans match the scalar/batched engines to
bisection precision.  Bitwise equality is NOT guaranteed — XLA may fuse or
reorder float reductions (matmul accumulation in the water-fill, cumsum in
the sigma check), which can flip an oracle answer exactly at a feasibility
boundary; both engines still bracket the same optimum, so times, betas and
traffic agree within ~1e-9 relative (the tolerance
``benchmarks/check_engine_parity.py`` and ``tests/test_jax_engine.py``
enforce; tree choices (parents) are asserted equal on the seeded parity
instances).  Known scalar-oracle departures, all documented here:

* ``witness="lp"`` is rejected (scipy cannot run inside jit) — use the
  batched/scalar engines for the LP witness oracle;
* the level-cut witness cannot raise on an infeasible live lane the way
  ``witness.min_level_batch`` does (no exceptions inside jit); callers get
  the same clamped-at-zero level instead.  The planners only evaluate the
  witness at a certified-feasible time, so the guard is unreachable on the
  planner path anyway.

Batch shapes are padded to the next power of two (lanes are provably
independent in every kernel — the water-fill's freeze rounds and all
``.any()``-driven loops are per-lane masked — so padding never changes real
lanes' results) to keep recompilation logarithmic in the number of distinct
batch sizes a fleet run produces.

Performance, measured honestly (1-core CPU container, fr/ftr at the
BENCH_planning profile config — see the ``engine_jax`` section of
BENCH_planning.json for the numbers of record): eliminating Python
dispatch does NOT make this tier faster than the NumPy engine here.  The
XLA per-row cost of the water-fill oracle is ~3.5x NumPy's SIMD row cost
with no fixed overhead to amortize, and a lockstep jit program cannot
compact converged lanes out of the batch the way the NumPy engine's mode
machines do, so ftr typically runs ~2-10x *slower* per plan on this
hardware (fr is roughly at parity at moderate batch sizes).  Variants
that were tried and measured worse on CPU, kept out on purpose:
speculative 2^L-way bisection (widens every oracle row 2^L-1x — loses
whenever the oracle is row-bound, which it is here), and trace-time
unrolling of the water-fill rounds in place of ``lax.while_loop`` (XLA
has no early exit, so all d rounds always run: 2.5-8x slower and up to
~97 s compile at d=19).  The value of this tier on CPU is the
parity-guarded portability of the planners to accelerator backends
(one ``jax.jit`` away from GPU/TPU, where lane width is ~free), not a
CPU speedup.
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import enable_x64, lax

from .batched import BatchPlanResult, _star_parents
from .ftr import (EVAL_ITERS as _EVAL_ITERS, FINAL_ITERS as _FINAL_ITERS,
                  LOCAL_SEARCH_ALTS as _MAX_ALTS,
                  LOCAL_SEARCH_ROUNDS as _MAX_ROUNDS,
                  PROBE_SLACK as _PROBE_SLACK, REFINE_ITERS as _REFINE_ITERS)
from .lp import BISECT_ITERS as _STAR_ITERS
from .params import CodeParams
from .regions import FeasibleRegion, heuristic_region, msr_region

__all__ = ["plan_fr_jax", "plan_ftr_jax", "plan_star_jax", "plan_tr_jax"]


def _region_for(params: CodeParams,
                region: Optional[FeasibleRegion]) -> FeasibleRegion:
    if region is None:
        return msr_region(params) if params.is_msr else heuristic_region(params)
    return region


def _check_witness(witness: str) -> None:
    if witness != "exact":
        raise ValueError(
            f"engine='jax' supports witness='exact' only (got {witness!r}); "
            f"use engine='batched' or 'scalar' for the LP witness oracle")


def _pad_pow2(B: int) -> int:
    """Next power of two >= B: pad lanes are benign and sliced away, and the
    jit cache stays logarithmic in the number of distinct fleet batch sizes."""
    return 1 << max(0, int(B - 1).bit_length())


def _pad_caps(caps: np.ndarray) -> np.ndarray:
    """Pad the batch axis to a power of two with all-ones overlays (valid,
    always-feasible networks; every kernel is lane-independent)."""
    B, D1, _ = caps.shape
    P = _pad_pow2(B)
    if P == B:
        return caps
    pad = np.ones((P - B, D1, D1))
    idx = np.arange(D1)
    pad[:, idx, idx] = 0.0
    return np.concatenate([caps, pad], axis=0)


# ---------------------------------------------------------------------------
# Shared jit-side primitives (traced inside the planner kernels)
# ---------------------------------------------------------------------------

def _subtree_masks(parents):
    """JAX port of ``batched.subtree_masks``: pointer-doubling transitive
    closure of the parent relation.  parents (P, D1) int -> (P, D1, d)."""
    P, D1 = parents.shape
    node = jnp.arange(D1)
    C = jnp.zeros((P, D1, D1))
    C = C.at[:, node, node].set(1.0)
    C = C.at[jnp.arange(P)[:, None], node[None, 1:], parents[:, 1:]].set(1.0)
    steps = 1
    while steps < D1:               # static python loop: log2(D1) squarings
        C = ((C @ C) > 0).astype(C.dtype)
        steps *= 2
    return jnp.swapaxes(C, 1, 2)[:, :, 1:]


def _edge_caps(caps, parents):
    """edge_caps[p, u-1] = c(u, parent(u)) for each lane's full tree."""
    P, D1 = parents.shape
    return caps[jnp.arange(P)[:, None], jnp.arange(1, D1)[None, :],
                parents[:, 1:]]


def _nest(inc):
    """Laminar nesting relation: boolean Gram matrix (see batched._nest_of)."""
    return (inc @ jnp.swapaxes(inc, 1, 2)) > 0


def _sigma_feasible(beta, x, tol):
    """Theorem-1 region check: sigma_j(beta) >= x_j - tol for all j."""
    d = beta.shape[-1]
    k = x.shape[0]
    sig = jnp.cumsum(jnp.sort(beta, axis=-1), axis=-1)[..., d - k:]
    return jnp.all(sig >= x - tol, axis=-1)


def _waterfill(inc, bnd, alpha, chain):
    """Lockstep leximin water-fill, mirroring ``batched.waterfill_batch``
    round for round (chain-minimal saturated sets freeze together; the
    terminal round alpha-fills all still-active coordinates).

    The loop is data-dependent (every round freezes at least one active
    coordinate per lane, so it runs at most d+1 rounds); a ``while_loop``
    keeps the average ~3-5 rounds instead of always paying d — measured
    2.5-8x faster than a trace-time unroll of d rounds on CPU-XLA."""
    P, S, d = inc.shape
    athr = alpha - 1e-15

    def body(st):
        v, active, _ = st
        X = jnp.stack([active, v * (1.0 - active)], axis=-1)     # (P, d, 2)
        Y = inc @ X                      # (active counts, frozen sums)
        na = Y[..., 0]
        cand = jnp.where(na == 0, jnp.inf,
                         (bnd - Y[..., 1]) / jnp.maximum(na, 1.0))
        freezable = cand < athr
        any_f = freezable.any()
        chmin = jnp.min(jnp.where(chain, cand[:, None, :], jnp.inf), axis=2)
        setfreeze = freezable & (cand <= chmin)
        any_set = setfreeze.any(axis=1)
        lamx = jnp.min(jnp.where(setfreeze[:, :, None] & (inc > 0),
                                 cand[:, :, None], jnp.inf), axis=1)
        lamx = jnp.maximum(lamx, 0.0)
        fin = lamx < jnp.inf
        mfrz = fin | ~any_set[:, None]
        lvl = jnp.where(fin, lamx, alpha)
        v_frz = jnp.where(mfrz & (active > 0), lvl, v)
        a_frz = active * (1.0 - mfrz)
        v_term = jnp.where(active > 0, alpha, v)
        v_new = jnp.where(any_f, v_frz, v_term)
        a_new = jnp.where(any_f, a_frz, jnp.zeros_like(active))
        done = ~any_f | ~(a_new > 0).any()
        return v_new, a_new, done

    init = (jnp.zeros((P, d)), jnp.ones((P, d)), jnp.asarray(False))
    v, _, _ = lax.while_loop(lambda st: ~st[2], body, init)
    return v


def _tree_feasible(t, mask, ec, x, alpha, chain):
    """``batched.tree_feasible_batch``: binding edges (t*c < alpha - 1e-12)
    bound their subtree sums; the water-fill point is checked against the
    region thresholds at the scalar oracle's 1e-9 tolerance."""
    bounds = t[:, None] * ec
    bnd = jnp.where(bounds < alpha - 1e-12, bounds, jnp.inf)
    wf = _waterfill(mask[:, 1:, :], bnd, alpha, chain)
    return _sigma_feasible(wf, x, 1e-9), wf


def _min_level(ub, x):
    """Exact minimal level cut (``witness.min_level_batch`` minus the
    infeasible-lane raise, which cannot exist inside jit)."""
    B, d = ub.shape
    k = x.shape[0]
    s = jnp.sort(ub, axis=1)
    S = jnp.concatenate([jnp.zeros((B, 1)), jnp.cumsum(s, axis=1)], axis=1)
    p = jnp.arange(d)
    m = d - k + jnp.arange(1, k + 1)
    denom = (m[None, :, None] - p[None, None, :]).astype(s.dtype)
    cand = (x[None, :, None] - S[:, None, :d]) / denom
    cand = jnp.where(denom > 0, cand, -jnp.inf)
    return jnp.maximum(jnp.max(cand, axis=(1, 2)), 0.0)


def _level_cut(ub, x):
    return jnp.minimum(ub, _min_level(ub, x)[:, None])


def _star_time(flows, direct):
    return jnp.max(jnp.where(direct > 0, flows / direct, jnp.inf), axis=1)


# ---------------------------------------------------------------------------
# STAR / FR
# ---------------------------------------------------------------------------

@jax.jit
def _star_kernel(direct, beta, alpha):
    B, d = direct.shape
    f = jnp.minimum(beta, alpha)
    flows = jnp.full((B, d), f)
    return (_star_time(flows, direct), flows.sum(axis=1),
            jnp.full((B, d), beta))


def _star_optimal_time(direct, x, alpha, lanes):
    """``batched.minmax_time_star_batch``: bisection on the coordinate-wise
    max point, 1e-12 region tolerance, hi-doubling giving up past 1e18."""
    B, d = direct.shape

    def feas(t):
        bh = jnp.minimum(t[:, None] * direct, alpha)
        return _sigma_feasible(bh, x, 1e-12)

    hi = jnp.ones(B)
    ok = feas(hi) | ~lanes

    def dbody(st):
        hi, ok = st
        hi = jnp.where(ok, hi, hi * 2.0)
        ok = ok | (hi > 1e18) | feas(hi)
        return hi, ok

    hi, _ = lax.while_loop(lambda st: ~st[1].all(), dbody, (hi, ok))
    dead = lanes & (hi > 1e18)
    lo = jnp.zeros(B)

    def bbody(_, st):
        lo, hi = st
        mid = 0.5 * (lo + hi)
        f = feas(mid)
        return jnp.where(f, lo, mid), jnp.where(f, mid, hi)

    lo, hi = lax.fori_loop(0, _STAR_ITERS, bbody, (lo, hi))
    return jnp.where(dead, jnp.inf, hi)


@functools.partial(jax.jit, static_argnames=("is_msr", "minimize_traffic"))
def _fr_kernel(direct, x, alpha, M, is_msr, minimize_traffic):
    B, d = direct.shape
    k = x.shape[0]
    betas = jnp.zeros((B, d))
    lb = jnp.zeros(B)
    closed = jnp.zeros(B, dtype=bool)
    if is_msr:
        # MSR closed form (star.fr_closed_form_msr) on all-positive lanes
        closed = (direct > 0).all(axis=1)
        m = d - k + 1
        safe = jnp.where(closed[:, None], direct, 1.0)
        order = jnp.argsort(safe, axis=1, stable=True)
        csort = jnp.take_along_axis(safe, order, axis=1)
        denom = csort[:, :m].sum(axis=1)
        rank = jnp.arange(d)[None, :]
        bsort = (jnp.where(rank < m, csort, csort[:, m - 1:m])
                 * M / (k * denom[:, None]))
        inv = jnp.argsort(order, axis=1, stable=True)
        cb = jnp.take_along_axis(bsort, inv, axis=1)
        ct = (cb / safe).max(axis=1)
        betas = jnp.where(closed[:, None], cb, betas)
        lb = jnp.where(closed, ct, lb)
    rest = ~closed
    t_rest = _star_optimal_time(direct, x, alpha, rest)
    lb = jnp.where(rest, t_rest, lb)
    live = rest & jnp.isfinite(t_rest)
    if minimize_traffic:
        ub = jnp.minimum(jnp.where(live, t_rest, 0.0)[:, None] * direct, alpha)
        wb = _level_cut(ub, x)
    else:
        wb = jnp.minimum(jnp.where(live, t_rest, 0.0)[:, None] * direct, alpha)
    betas = jnp.where(live[:, None], wb, betas)
    flows = jnp.minimum(betas, alpha)
    times = jnp.maximum(_star_time(flows, direct), 0.0)
    bad = ~jnp.isfinite(lb)
    times = jnp.where(bad, jnp.inf, times)
    traffic = jnp.where(bad, jnp.inf, flows.sum(axis=1))
    return times, traffic, betas, lb


# ---------------------------------------------------------------------------
# TR — Algorithm 1 (incremental greedy, lockstep)
# ---------------------------------------------------------------------------

def _tr_greedy(caps, beta, alpha):
    """The d-step greedy of ``batched.plan_tr_batch`` with the identical
    lexicographic (t, -c(v,u), v, u) candidate selection."""
    B, D1, _ = caps.shape
    d = D1 - 1
    bidx = jnp.arange(B)
    new_flow = jnp.minimum(beta, alpha)
    new_edge_t = jnp.where(caps > 0, new_flow / caps, jnp.inf)

    def body(_, st):
        parent, attached, anc, size, edge_c = st
        att_e = attached.at[:, 0].set(False)
        f_now = jnp.minimum(size * beta, alpha)
        f_inc = jnp.minimum((size + 1.0) * beta, alpha)
        h = jnp.where(att_e, jnp.where(edge_c > 0, f_now / edge_c, jnp.inf),
                      -jnp.inf)
        g = jnp.where(att_e, jnp.where(edge_c > 0, f_inc / edge_c, jnp.inf),
                      -jnp.inf)
        val = jnp.where(anc, g[:, :, None], h[:, :, None])
        T_path = jnp.maximum(val.max(axis=1), 0.0)
        cand_t = jnp.maximum(new_edge_t, T_path[:, None, :])
        valid = (~attached)[:, :, None] & attached[:, None, :]
        cand_t = jnp.where(valid, cand_t, jnp.inf)
        tmin = cand_t.min(axis=(1, 2))
        is_t = valid & (cand_t == tmin[:, None, None])
        cgrid = jnp.where(is_t, caps, -jnp.inf)
        cmax = cgrid.max(axis=(1, 2))
        sel = is_t & (cgrid == cmax[:, None, None])
        choice = jnp.argmax(sel.reshape(B, -1), axis=1)
        v_sel = choice // D1
        u_sel = choice % D1
        parent = parent.at[bidx, v_sel].set(u_sel.astype(parent.dtype))
        attached = attached.at[bidx, v_sel].set(True)
        edge_c = edge_c.at[bidx, v_sel].set(caps[bidx, v_sel, u_sel])
        size = size + anc[bidx, :, u_sel]
        size = size.at[bidx, v_sel].set(1.0)
        anc = anc.at[bidx, :, v_sel].set(anc[bidx, :, u_sel])
        anc = anc.at[bidx, v_sel, v_sel].set(True)
        return parent, attached, anc, size, edge_c

    init = (jnp.zeros((B, D1), dtype=jnp.int32),
            jnp.zeros((B, D1), dtype=bool).at[:, 0].set(True),
            jnp.zeros((B, D1, D1), dtype=bool),
            jnp.zeros((B, D1)),
            jnp.zeros((B, D1)))
    parent, _, _, size, edge_c = lax.fori_loop(0, d, body, init)
    return parent, size, edge_c


@jax.jit
def _tr_kernel(caps, beta, alpha):
    parent, size, edge_c = _tr_greedy(caps, beta, alpha)
    flows = jnp.minimum(size[:, 1:] * beta, alpha)
    et = jnp.where(edge_c[:, 1:] > 0, flows / edge_c[:, 1:], jnp.inf)
    return et.max(axis=1), flows.sum(axis=1), parent


# ---------------------------------------------------------------------------
# FTR — Algorithm 2 (candidate population + pivot local search), lockstep
# ---------------------------------------------------------------------------

def _ftr_candidates(caps, tr_parents):
    """``batched._ftr_candidates``: one core-growth pass (prefix property),
    then every core size i = 0..d as a candidate, plus the TR tree."""
    B, D1, _ = caps.shape
    d = D1 - 1
    bidx = jnp.arange(B)

    def gbody(step, st):
        in_core, core_pos, parfull = st
        cuv = jnp.where(~in_core[:, :, None] & in_core[:, None, :], caps,
                        -jnp.inf)
        cuv = cuv.at[:, 0, :].set(-jnp.inf)
        rowbest = cuv.max(axis=2)
        u_sel = jnp.argmax(rowbest, axis=1)
        best = rowbest[bidx, u_sel]
        pos = jnp.where(cuv[bidx, u_sel, :] == best[:, None], core_pos,
                        D1 + 2)
        v_sel = jnp.argmin(pos, axis=1)
        parfull = parfull.at[bidx, u_sel].set(v_sel.astype(parfull.dtype))
        in_core = in_core.at[bidx, u_sel].set(True)
        core_pos = core_pos.at[bidx, u_sel].set(
            (step + 1).astype(core_pos.dtype))
        return in_core, core_pos, parfull

    init = (jnp.zeros((B, D1), dtype=bool).at[:, 0].set(True),
            jnp.full((B, D1), D1 + 1, dtype=jnp.int32).at[:, 0].set(0),
            jnp.zeros((B, D1), dtype=jnp.int32))
    _, core_pos, parfull = lax.fori_loop(0, d, gbody, init)

    ii = jnp.arange(d + 1)[None, :, None]                     # (1, d+1, 1)
    mask_core = core_pos[:, None, :] <= ii                    # (B, d+1, D1)
    cu = jnp.where(mask_core[:, :, None, :], caps[:, None, :, :], -jnp.inf)
    mx = cu.max(axis=3)
    posg = jnp.where(cu == mx[..., None], core_pos[:, None, None, :], D1 + 2)
    vbest = jnp.argmin(posg, axis=3).astype(jnp.int32)        # (B, d+1, u)
    par = jnp.where(mask_core, parfull[:, None, :], vbest)
    par = par.at[:, :, 0].set(0)
    return jnp.concatenate([par, tr_parents[:, None, :].astype(par.dtype)],
                           axis=1)                            # (B, d+2, D1)


def _candidate_times(caps, cands, x, alpha):
    """Per-candidate optimal times with the scalar planner's incumbent
    pruning, lockstep over candidates: candidate c is probed at the lane's
    incumbent (refine 28 iters on accept, inf on reject); lanes with no
    finite incumbent run the full 40-iter solve.  Duplicate and
    zero-capacity candidates are skipped exactly as the NumPy engine's."""
    B, C, D1 = cands.shape
    d = D1 - 1
    flat = cands.reshape(B * C, D1)
    mask_all = _subtree_masks(flat)
    lane_of = jnp.repeat(jnp.arange(B), C)
    ec_all = caps[lane_of[:, None], jnp.arange(1, D1)[None, :], flat[:, 1:]]
    chain_all = _nest(mask_all[:, 1:, :])
    eq = (cands[:, :, None, :] == cands[:, None, :, :]).all(axis=-1)
    dup = (eq & jnp.tril(jnp.ones((C, C), dtype=bool), -1)[None]).any(axis=2)
    ec_ok = (ec_all > 0).all(axis=1).reshape(B, C)
    hi0_all = ((alpha / jnp.where(ec_all > 0, ec_all, 1.0)).max(axis=1)
               * (1 + 1e-9) + 1e-12).reshape(B, C)
    mask_r = mask_all.reshape(B, C, D1, d)
    ec_r = ec_all.reshape(B, C, d)
    ch_r = chain_all.reshape(B, C, d, d)

    def cbody(c, st):
        t_cand, incumbent = st
        m = lax.dynamic_index_in_dim(mask_r, c, 1, keepdims=False)
        ec = lax.dynamic_index_in_dim(ec_r, c, 1, keepdims=False)
        ch = lax.dynamic_index_in_dim(ch_r, c, 1, keepdims=False)
        okl = (~lax.dynamic_index_in_dim(dup, c, 1, keepdims=False)
               & lax.dynamic_index_in_dim(ec_ok, c, 1, keepdims=False))
        hi0 = lax.dynamic_index_in_dim(hi0_all, c, 1, keepdims=False)
        has_inc = jnp.isfinite(incumbent)
        probe_lane = okl & has_inc
        full_lane = okl & ~has_inc
        pf, _ = _tree_feasible(jnp.where(probe_lane, incumbent, 1.0), m, ec,
                               x, alpha, ch)
        pf = pf & probe_lane
        hi = jnp.where(full_lane, hi0, jnp.where(pf, incumbent, 1.0))
        f0, _ = _tree_feasible(jnp.where(full_lane, hi, 1.0), m, ec, x,
                               alpha, ch)
        feasd = f0 & full_lane
        need0 = full_lane & ~feasd

        def dbody(dst):
            hi, feasd, need = dst
            hi = jnp.where(need, hi * 2.0, hi)
            over = hi >= 1e18
            f2, _ = _tree_feasible(jnp.where(need & ~over, hi, 1.0), m, ec,
                                   x, alpha, ch)
            feasd = feasd | (need & ~over & f2)
            return hi, feasd, need & ~feasd & ~over

        hi, feasd, _ = lax.while_loop(lambda dst: dst[2].any(), dbody,
                                      (hi, feasd, need0))
        solve = pf | feasd
        budget = jnp.where(full_lane, _EVAL_ITERS, _REFINE_ITERS)
        lo = jnp.zeros_like(hi)

        def bbody(i, bst):
            lo, hi = bst
            on = solve & (i < budget)
            mid = 0.5 * (lo + hi)
            f, _ = _tree_feasible(jnp.where(on, mid, 1.0), m, ec, x, alpha,
                                  ch)
            return (jnp.where(on & ~f, mid, lo), jnp.where(on & f, mid, hi))

        lo, hi = lax.fori_loop(0, _EVAL_ITERS, bbody, (lo, hi))
        t_c = jnp.where(solve, hi, jnp.inf)
        t_cand = lax.dynamic_update_index_in_dim(t_cand, t_c, c, 1)
        return t_cand, jnp.minimum(incumbent, t_c)

    t_cand, _ = lax.fori_loop(0, C, cbody,
                              (jnp.full((B, C), jnp.inf), jnp.full(B, jnp.inf)))
    return t_cand


def _tree_optimal_time(mask, ec, ch, x, alpha, iters, lanes):
    """``batched.tree_optimal_time_batch`` (lockstep, no lane compaction)."""
    B = ec.shape[0]
    valid = lanes & (ec > 0).all(axis=1)
    safe = jnp.where(ec > 0, ec, 1.0)
    hi = jnp.where(valid, (alpha / safe).max(axis=1) * (1 + 1e-9) + 1e-12,
                   jnp.inf)
    feas, _ = _tree_feasible(jnp.where(valid, hi, 1.0), mask, ec, x, alpha,
                             ch)
    feas = feas & valid
    need0 = valid & ~feas

    def dbody(dst):
        hi, feas, need = dst
        hi = jnp.where(need, hi * 2.0, hi)
        over = hi >= 1e18
        f2, _ = _tree_feasible(jnp.where(need & ~over, hi, 1.0), mask, ec,
                               x, alpha, ch)
        feas = feas | (need & ~over & f2)
        return hi, feas, valid & ~feas & ~over

    hi, feas, _ = lax.while_loop(lambda dst: dst[2].any(), dbody,
                                 (hi, feas, need0))
    live = valid & feas
    lo = jnp.zeros(B)

    def bbody(_, bst):
        lo, hi = bst
        mid = 0.5 * (lo + hi)
        f, _ = _tree_feasible(jnp.where(live, mid, 1.0), mask, ec, x, alpha,
                              ch)
        return (jnp.where(live & ~f, mid, lo), jnp.where(live & f, mid, hi))

    lo, hi = lax.fori_loop(0, iters, bbody, (lo, hi))
    return jnp.where(live, hi, jnp.inf)


def _local_search(caps, parents, t_cur, x, alpha, alive):
    """``batched._local_search_batch`` in lockstep: rounds x nodes unrolled
    to a fixed ``fori_loop`` over (round, u) steps with a per-lane
    ``running`` mask; within a step, probe waves over the node's untried
    alternatives run data-dependently (``while_loop``), first feasible
    alternative accepted, refine [0, t_cur] on accept, remaining
    alternatives replayed on the updated tree — the scalar pivot sweep's
    exact decision sequence."""
    L, D1 = parents.shape
    d = D1 - 1
    A = min(_MAX_ALTS, D1)
    lidx = jnp.arange(L)
    bm0 = _subtree_masks(parents)
    ec0 = _edge_caps(caps, parents)
    ch0 = _nest(bm0[:, 1:, :])
    root_onehot = jnp.zeros(D1).at[0].set(1.0)

    def step(s, st):
        parents, bm, ec, ch, t_cur, improved, running = st
        u = s % d + 1
        cpu = caps[:, u, :]                         # (L, D1), dynamic gather
        dsc = bm[:, u, :]                           # (L, d)
        pw = parents[:, u]
        nodes = jnp.arange(D1)[None, :]
        in_sub = jnp.concatenate([jnp.zeros((L, 1)), dsc], axis=1)
        ok = ((cpu > 0) & (nodes != u) & (nodes != pw[:, None])
              & ~(in_sub > 0))
        nok = jnp.minimum(ok.sum(axis=1), _MAX_ALTS)
        ordw = jnp.argsort(jnp.where(ok, -cpu, jnp.inf), axis=1,
                           stable=True)[:, :A]

        def wbody(wst):
            parents, bm, ec, ch, t_cur, improved, jj = wst
            aidx = jnp.arange(A)[None, :]
            validA = (aidx >= jj[:, None]) & (aidx < nok[:, None])
            palt = ordw                                        # (L, A)
            # one-edge mask update (the NumPy engine's incremental formula):
            # u's descendants keep their in-subtree ancestors and adopt the
            # new parent's ancestor chain; all other chains are untouched
            anc_v = jnp.where((palt >= 1)[:, :, None],
                              bm[lidx[:, None], :, jnp.maximum(palt - 1, 0)],
                              root_onehot[None, None, :])      # (L, A, D1)
            pmask = jnp.where(
                dsc[:, None, None, :] > 0,
                jnp.minimum(bm[:, None, :, :] * in_sub[:, None, :, None]
                            + anc_v[..., None], 1.0),
                bm[:, None, :, :])                             # (L, A, D1, d)
            newc = jnp.take_along_axis(cpu, palt, axis=1)      # (L, A)
            colu = jnp.arange(d)[None, None, :] == (u - 1)
            pec = jnp.where(colu, newc[:, :, None], ec[:, None, :])
            flatm = pmask.reshape(L * A, D1, d)
            flate = pec.reshape(L * A, d)
            flatch = _nest(flatm[:, 1:, :])
            tq = jnp.where(validA, (t_cur * _PROBE_SLACK)[:, None],
                           1.0).reshape(L * A)
            fq, _ = _tree_feasible(tq, flatm, flate, x, alpha, flatch)
            fA = fq.reshape(L, A) & validA
            acc = fA.any(axis=1)
            jstar = jnp.argmax(fA, axis=1)                 # first feasible
            vnew = jnp.take_along_axis(palt, jstar[:, None], axis=1)[:, 0]
            parents = parents.at[lidx, u].set(
                jnp.where(acc, vnew, parents[:, u]).astype(parents.dtype))
            selm = pmask[lidx, jstar]
            sele = pec[lidx, jstar]
            selch = flatch.reshape(L, A, d, d)[lidx, jstar]
            bm = jnp.where(acc[:, None, None], selm, bm)
            ec = jnp.where(acc[:, None], sele, ec)
            ch = jnp.where(acc[:, None, None], selch, ch)

            def rbody(_, rst):
                lo, hi = rst
                mid = 0.5 * (lo + hi)
                f, _ = _tree_feasible(jnp.where(acc, mid, 1.0), bm, ec, x,
                                      alpha, ch)
                return (jnp.where(acc & ~f, mid, lo),
                        jnp.where(acc & f, mid, hi))

            def do_refine(t_cur):
                _, hi = lax.fori_loop(0, _REFINE_ITERS, rbody,
                                      (jnp.zeros(L), t_cur))
                return jnp.where(acc, hi, t_cur)

            t_cur = lax.cond(acc.any(), do_refine, lambda t: t, t_cur)
            improved = improved | acc
            jj = jnp.where(acc, jstar + 1, nok)
            return parents, bm, ec, ch, t_cur, improved, jj

        jj0 = jnp.where(running, 0, nok)
        parents, bm, ec, ch, t_cur, improved, _ = lax.while_loop(
            lambda wst: (wst[6] < nok).any(), wbody,
            (parents, bm, ec, ch, t_cur, improved, jj0))
        at_end = (s % d) == (d - 1)
        running = jnp.where(at_end, running & improved, running)
        improved = jnp.where(at_end, jnp.zeros_like(improved), improved)
        return parents, bm, ec, ch, t_cur, improved, running

    init = (parents, bm0, ec0, ch0, t_cur, jnp.zeros(L, dtype=bool), alive)
    parents, _, _, _, t_cur, _, _ = lax.fori_loop(0, _MAX_ROUNDS * d, step,
                                                  init)
    return parents, t_cur


@functools.partial(jax.jit, static_argnames=("local_search",))
def _ftr_kernel(caps, x, alpha, beta_u, local_search):
    B, D1, _ = caps.shape
    d = D1 - 1
    bidx = jnp.arange(B)
    tr_parent, _, _ = _tr_greedy(caps, beta_u, alpha)
    cands = _ftr_candidates(caps, tr_parent)
    t_cand = _candidate_times(caps, cands, x, alpha)
    order = jnp.argsort(t_cand, axis=1, stable=True)
    best_t = jnp.take_along_axis(t_cand, order[:, :1], axis=1)[:, 0]
    best_par = cands[bidx, order[:, 0]]
    if local_search:
        top = order[:, :3]
        par_ls = cands[bidx[:, None], top].reshape(B * 3, D1)
        t_ls = jnp.take_along_axis(t_cand, top, axis=1).reshape(B * 3)
        caps_ls = jnp.repeat(caps, 3, axis=0)
        par_ls, t_ls = _local_search(caps_ls, par_ls, t_ls, x, alpha,
                                     jnp.isfinite(t_ls))
        par_ls = par_ls.reshape(B, 3, D1)
        t_ls = t_ls.reshape(B, 3)
        for s in range(3):                  # winner update order: s = 0,1,2
            upd = t_ls[:, s] < best_t
            best_t = jnp.where(upd, t_ls[:, s], best_t)
            best_par = jnp.where(upd[:, None], par_ls[:, s], best_par)
    mask = _subtree_masks(best_par)
    ec = _edge_caps(caps, best_par)
    ch = _nest(mask[:, 1:, :])
    solvable = jnp.isfinite(best_t)
    t_star = _tree_optimal_time(mask, ec, ch, x, alpha, _FINAL_ITERS,
                                solvable)
    _, wf = _tree_feasible(jnp.where(solvable, t_star, 1.0), mask, ec, x,
                           alpha, ch)
    betas = jnp.where(solvable[:, None], _level_cut(wf, x), 0.0)
    sub = jnp.einsum("bud,bd->bu", mask[:, 1:, :], betas)
    flows = jnp.minimum(sub, alpha)
    et = jnp.where(ec > 0, flows / ec, jnp.inf)
    times = jnp.where(solvable, et.max(axis=1), jnp.inf)
    traffic = jnp.where(solvable, flows.sum(axis=1), jnp.inf)
    return times, traffic, betas, best_par, t_star


# ---------------------------------------------------------------------------
# Public planners (the SchemeSpec.jax entries)
# ---------------------------------------------------------------------------

def plan_star_jax(caps: np.ndarray, params: CodeParams) -> BatchPlanResult:
    """Jit-compiled ``plan_star_batch``."""
    caps = np.asarray(caps, dtype=np.float64)
    B, _, _ = caps.shape
    d = params.d
    with enable_x64():
        t, tr, be = _star_kernel(jnp.asarray(_pad_caps(caps)[:, 1:, 0]),
                                 float(params.beta), float(params.alpha))
        t, tr, be = (np.asarray(a)[:B] for a in (t, tr, be))
    return BatchPlanResult("star", t, tr, be, _star_parents(B, d),
                           engine="jax")


def plan_fr_jax(caps: np.ndarray, params: CodeParams,
                region: Optional[FeasibleRegion] = None,
                minimize_traffic: bool = True,
                witness: str = "exact") -> BatchPlanResult:
    """Jit-compiled ``plan_fr_batch`` (closed form at MSR, lockstep star
    bisection + level-cut witness elsewhere)."""
    _check_witness(witness)
    region = _region_for(params, region)
    caps = np.asarray(caps, dtype=np.float64)
    B, _, _ = caps.shape
    d = params.d
    x = np.asarray(region.x, dtype=np.float64)
    with enable_x64():
        t, tr, be, lb = _fr_kernel(jnp.asarray(_pad_caps(caps)[:, 1:, 0]),
                                   jnp.asarray(x), float(params.alpha),
                                   float(params.M), is_msr=params.is_msr,
                                   minimize_traffic=bool(minimize_traffic))
        t, tr, be, lb = (np.asarray(a)[:B] for a in (t, tr, be, lb))
    return BatchPlanResult("fr", t, tr, be, _star_parents(B, d),
                           lower_bounds=lb, engine="jax")


def plan_tr_jax(caps: np.ndarray, params: CodeParams) -> BatchPlanResult:
    """Jit-compiled ``plan_tr_batch`` (Algorithm 1)."""
    caps = np.asarray(caps, dtype=np.float64)
    B, _, _ = caps.shape
    d = params.d
    with enable_x64():
        t, tr, par = _tr_kernel(jnp.asarray(_pad_caps(caps)),
                                float(params.beta), float(params.alpha))
        t, tr = np.asarray(t)[:B], np.asarray(tr)[:B]
        par = np.asarray(par)[:B].astype(np.int64)
    return BatchPlanResult("tr", t, tr, np.full((B, d), params.beta), par,
                           engine="jax")


def plan_ftr_jax(caps: np.ndarray, params: CodeParams,
                 region: Optional[FeasibleRegion] = None,
                 local_search: bool = True,
                 witness: str = "exact") -> BatchPlanResult:
    """Jit-compiled ``plan_ftr_batch`` (Algorithm 2 + pivot search + final
    50-iteration solve + level-cut witness)."""
    _check_witness(witness)
    region = _region_for(params, region)
    caps = np.asarray(caps, dtype=np.float64)
    B, _, _ = caps.shape
    x = np.asarray(region.x, dtype=np.float64)
    with enable_x64():
        t, tr, be, par, lbs = _ftr_kernel(
            jnp.asarray(_pad_caps(caps)), jnp.asarray(x),
            float(params.alpha), float(params.beta),
            local_search=bool(local_search))
        t, tr, be, lbs = (np.asarray(a)[:B] for a in (t, tr, be, lbs))
        par = np.asarray(par)[:B].astype(np.int64)
    return BatchPlanResult("ftr", t, tr, be, par, lower_bounds=lbs,
                           engine="jax")
