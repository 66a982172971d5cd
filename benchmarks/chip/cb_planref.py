"""Plain reference of the four repair planners at the MSR point.

One overlay at a time, in scalar arithmetic, as the paper defines them:
STAR (uniform beta), FR (closed form of problem (4), Section III-B), TR
(Algorithm 1, Section IV) and FTR (Algorithm 2 with pivot search, Section
V).  It follows the repository's scalar oracle decision for decision, so
that it picks the same trees; it imports nothing of the program.

``dtype`` sets the precision: ``float`` (Python floats, float64) is the
reference, ``numpy.float32`` is the control that one precision lower must
fail.  Capacities, alpha, M and thresholds enter in that type, and numpy's
scalar promotion keeps every operation in it.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

# search depths and slack of FTR (Algorithm 2's implementation in the repo)
EVAL_ITERS = 40
REFINE_ITERS = 28
FINAL_ITERS = 50
LOCAL_SEARCH_ROUNDS = 3
LOCAL_SEARCH_ALTS = 8
PROBE_SLACK = 1 - 1e-7
WITNESS_TOL = 1e-7


def uniform_beta(M: float, k: int, d: int, alpha: float) -> float:
    """Theorem 3: the least b with sum_j min((d-k+j) b, alpha) = M."""
    for s in range(k + 1):
        mult = sum(d - k + j for j in range(1, k - s + 1))
        if mult == 0:
            b = alpha / max(d - k + 1, 1)
            if s * alpha >= M - 1e-9:
                return b
            continue
        b = (M - s * alpha) / mult
        if b < -1e-12:
            continue
        b = max(b, 0.0)
        ok = True
        for j in range(1, k + 1):
            sat = (d - k + j) * b >= alpha * (1 - 1e-12)
            if sat != (j > k - s) and \
                    abs((d - k + j) * b - alpha) > 1e-9 * max(alpha, 1.0):
                ok = False
                break
        if ok:
            return b
    raise ArithmeticError("no uniform beta")


def tree_flows(parent: Dict[int, int], betas: Sequence, alpha) -> Dict:
    """f(u, parent(u)) = min(beta_u + sum of children's flows, alpha)."""
    children: Dict[int, List[int]] = {}
    for u, p in parent.items():
        children.setdefault(p, []).append(u)
    sub: Dict[int, float] = {}

    def visit(u):
        s = betas[u - 1]
        for c in children.get(u, []):
            s += min(visit(c), alpha)
        sub[u] = s
        return s

    for r in children.get(0, []):
        visit(r)
    return {(u, p): min(sub[u], alpha) for u, p in parent.items()}


class Plan:
    def __init__(self, parent, betas, flows, time, lower_bound=None):
        self.parent, self.betas, self.flows = parent, betas, flows
        self.time, self.lower_bound = time, lower_bound

    @property
    def traffic(self):
        return sum(self.flows.values())


class Planner:
    """The planners of one code (n, k, d, alpha = M/k) in one precision."""

    def __init__(self, k: int, d: int, M: float, dtype=float):
        self.f = dtype
        self.np_dtype = np.float64 if dtype is float else dtype
        self.k, self.d = k, d
        alpha = M / k
        self.alpha = dtype(alpha)
        self.M = dtype(M)
        self.beta = dtype(uniform_beta(M, k, d, alpha))
        # Theorem 2: at MSR only sigma_1 >= M/k binds; Theorem-1 form
        self.x = tuple([self.M / k] * k)

    # -- the region ---------------------------------------------------------

    def contains(self, beta, tol=1e-9) -> bool:
        s = sorted(beta)
        return all(sum(s[:self.d - self.k + j]) >= self.x[j - 1] - tol
                   for j in range(1, self.k + 1))

    def level_cut(self, ub) -> list:
        """Traffic-minimal point min(ub, lam*) under the region."""
        dt = self.np_dtype
        ub = np.asarray(ub, dtype=dt)
        d, k = self.d, self.k
        s = np.sort(ub)
        S = np.concatenate([np.zeros(1, dt), np.cumsum(s)])
        p = np.arange(d)
        m = d - k + np.arange(1, k + 1)
        x = np.asarray(self.x, dtype=dt)
        slack = x - S[m]
        if (slack > WITNESS_TOL * np.maximum(1.0, np.abs(x))).any():
            raise ValueError("infeasible at the coordinate-wise max point")
        denom = (m[:, None] - p[None, :]).astype(dt)
        with np.errstate(divide="ignore", invalid="ignore"):
            cand = (x[:, None] - S[None, :d]) / denom
        cand = np.where(denom > 0, cand, -np.inf)
        lam = max(cand.max(), dt(0.0))
        return [self.f(v) for v in np.minimum(ub, lam)]

    # -- STAR and FR --------------------------------------------------------

    def star(self, cap) -> Plan:
        d = self.d
        betas = [self.beta] * d
        parent = {i: 0 for i in range(1, d + 1)}
        flows = tree_flows(parent, betas, self.alpha)
        return Plan(parent, betas, flows, self._star_time(flows, cap))

    def _star_time(self, flows, cap):
        return max((flows[(i, 0)] / cap[i][0]) if cap[i][0] > 0 else math.inf
                   for i in range(1, self.d + 1))

    def fr(self, cap) -> Plan:
        d, k, M = self.d, self.k, self.M
        caps = [cap[i][0] for i in range(1, d + 1)]
        order = sorted(range(d), key=lambda i: caps[i])
        m = d - k + 1
        denom = self.f(0.0)
        for i in range(m):
            denom += caps[order[i]]
        betas = [self.f(0.0)] * d
        for rank, i in enumerate(order):
            c = caps[i] if rank < m else caps[order[m - 1]]
            betas[i] = c * M / (k * denom)
        lb = max(betas[i] / caps[i] for i in range(d))
        parent = {i: 0 for i in range(1, d + 1)}
        flows = tree_flows(parent, betas, self.alpha)
        return Plan(parent, betas, flows, max(self._star_time(flows, cap), 0.0),
                    lower_bound=lb)

    # -- TR -----------------------------------------------------------------

    def _tree_time(self, parent, betas, cap):
        t = 0.0
        for (u, v), fl in tree_flows(parent, betas, self.alpha).items():
            c = cap[u][v]
            if c <= 0:
                return math.inf
            t = max(t, fl / c)
        return t

    def tr(self, cap) -> Plan:
        d = self.d
        parent: Dict[int, int] = {}
        in_tree = {0}
        remaining = set(range(1, d + 1))
        while remaining:
            best, best_key = None, None
            for v in sorted(remaining):
                for u in sorted(in_tree):
                    cand = dict(parent)
                    cand[v] = u
                    betas = [self.f(0.0)] * d
                    for w in cand:
                        betas[w - 1] = self.beta
                    key = (self._tree_time(cand, betas, cap), -cap[v][u])
                    if best_key is None or key < best_key:
                        best, best_key = (v, u), key
            v, u = best
            parent[v] = u
            in_tree.add(v)
            remaining.discard(v)
        betas = [self.beta] * d
        flows = tree_flows(parent, betas, self.alpha)
        return Plan(parent, betas, flows, self._tree_time(parent, betas, cap))

    # -- FTR: fixed-tree oracle ---------------------------------------------

    def _subtrees(self, parent):
        children: Dict[int, List[int]] = {}
        for u, p in parent.items():
            children.setdefault(p, []).append(u)
        subs: Dict[int, List[int]] = {}

        def visit(u):
            acc = [u]
            for ch in children.get(u, []):
                acc.extend(visit(ch))
            subs[u] = acc
            return acc

        for r in children.get(0, []):
            visit(r)
        return subs

    def _waterfill(self, laminar) -> list:
        """Leximin-maximal vector under beta_i <= alpha and laminar subtree
        caps [(coordinates, bound), ...]."""
        dt = self.np_dtype
        d = self.d
        ub = np.full(d, self.alpha, dtype=dt)
        v = np.zeros(d, dt)
        active = np.ones(d, dtype=bool)
        inc = np.zeros((len(laminar), d), dtype=dt)
        bnd = np.zeros(len(laminar), dtype=dt)
        for si, (S, B) in enumerate(laminar):
            inc[si, S] = 1.0
            bnd[si] = B
        while active.any():
            lam = ub[active].min()
            freeze = -1
            if len(bnd):
                na = inc @ active.astype(dt)
                frozen = inc @ (v * ~active)
                with np.errstate(divide="ignore", invalid="ignore"):
                    cand = np.where(na > 0, (bnd - frozen) / np.maximum(na, 1),
                                    np.inf).astype(dt)
                si = int(np.argmin(cand))
                if cand[si] < lam - 1e-15:
                    lam, freeze = cand[si], si
            lam = max(lam, dt(0.0))
            if freeze >= 0:
                members = (inc[freeze] > 0) & active
                v[members] = lam
            else:
                members = active & (ub <= lam + 1e-15)
                v[members] = ub[members]
            active &= ~members
        return [self.f(x) for x in v]

    def _feasible(self, t, parent, cap, minimize_traffic=False):
        subs = self._subtrees(parent)
        laminar = []
        for u, p in parent.items():
            bound = t * cap[u][p]
            if bound >= self.alpha - 1e-12:
                continue
            laminar.append(([x - 1 for x in subs[u]], bound))
        wf = self._waterfill(laminar)
        if not self.contains(wf, tol=1e-9):
            return None
        return self.level_cut(wf) if minimize_traffic else wf

    def _optimal_time(self, parent, cap, iters, minimize_traffic=False):
        ecs = [cap[u][p] for u, p in parent.items()]
        if any(c <= 0 for c in ecs):
            return math.inf, None
        hi = max(self.alpha / c for c in ecs) * (1 + 1e-9) + 1e-12
        if self._feasible(hi, parent, cap) is None:
            while hi < 1e18:
                hi *= 2
                if self._feasible(hi, parent, cap) is not None:
                    break
            else:
                return math.inf, None
        lo, beta = 0.0, None
        for _ in range(iters):
            mid = 0.5 * (lo + hi)
            w = self._feasible(mid, parent, cap)
            if w is not None:
                hi, beta = mid, w
            else:
                lo = mid
        if minimize_traffic:
            w = self._feasible(hi, parent, cap, minimize_traffic=True)
            if w is not None:
                beta = w
        if beta is None:
            beta = self._feasible(hi, parent, cap)
        return hi, beta

    def _refine(self, parent, cap, t_ub):
        lo, hi = 0.0, t_ub
        for _ in range(REFINE_ITERS):
            mid = 0.5 * (lo + hi)
            if self._feasible(mid, parent, cap) is not None:
                hi = mid
            else:
                lo = mid
        return hi

    # -- FTR: tree search ---------------------------------------------------

    def _grow_core(self, cap, i):
        d = self.d
        core = [0]
        for _ in range(i):
            best_u, best_c = None, -1.0
            for u in range(1, d + 1):
                if u in core:
                    continue
                for v in core:
                    if cap[u][v] > best_c:
                        best_u, best_c = u, cap[u][v]
            if best_u is None:
                break
            core.append(best_u)
        return core

    def _initial_tree(self, cap, core):
        parent: Dict[int, int] = {}
        placed = [0]
        for u in core[1:]:
            parent[u] = max(placed, key=lambda v: cap[u][v])
            placed.append(u)
        for u in range(1, self.d + 1):
            if u not in core:
                parent[u] = max(core, key=lambda v: cap[u][v])
        return parent

    def _descendants(self, parent, u):
        desc = set()
        for w in range(1, self.d + 1):
            x = w
            while x != 0:
                if x == u:
                    desc.add(w)
                    break
                x = parent[x]
        return desc

    def _local_search(self, parent, cap, t_cur):
        d = self.d
        for _ in range(LOCAL_SEARCH_ROUNDS):
            improved = False
            for u in range(1, d + 1):
                desc = self._descendants(parent, u)
                cur_p = parent[u]
                alts = sorted((v for v in range(0, d + 1)
                               if v != u and v != cur_p and v not in desc
                               and cap[u][v] > 0),
                              key=lambda v: -cap[u][v])[:LOCAL_SEARCH_ALTS]
                for v in alts:
                    parent[u] = v
                    if self._feasible(t_cur * PROBE_SLACK, parent, cap) \
                            is not None:
                        t_cur = self._refine(parent, cap, t_cur)
                        cur_p = v
                        improved = True
                    else:
                        parent[u] = cur_p
            if not improved:
                break
        return parent, t_cur

    def ftr(self, cap) -> Plan:
        d = self.d
        cands = [self._initial_tree(cap, self._grow_core(cap, i))
                 for i in range(d + 1)]
        cands.append(dict(self.tr(cap).parent))
        scored: List[Tuple[float, Dict[int, int]]] = []
        seen = set()
        incumbent = math.inf
        for cand in cands:
            key = tuple(sorted(cand.items()))
            if key in seen:
                continue
            seen.add(key)
            if incumbent is math.inf:
                t, _ = self._optimal_time(cand, cap, EVAL_ITERS)
            elif self._feasible(incumbent, cand, cap) is not None:
                t = self._refine(cand, cap, incumbent)
            else:
                t = math.inf
            incumbent = min(incumbent, t)
            scored.append((t, cand))
        scored.sort(key=lambda x: x[0])
        best_t, best_parent = scored[0]
        for t, cand in scored[:3]:
            if t is math.inf:
                continue
            cand, t = self._local_search(dict(cand), cap, t)
            if t < best_t:
                best_parent, best_t = dict(cand), t
        t_star, betas = self._optimal_time(best_parent, cap, FINAL_ITERS,
                                           minimize_traffic=True)
        if betas is None:
            raise RuntimeError("FTR: the winning tree is infeasible")
        flows = tree_flows(best_parent, betas, self.alpha)
        time = 0.0
        for (u, v), fl in flows.items():
            c = cap[u][v]
            time = max(time, fl / c if c > 0 else math.inf)
        return Plan(best_parent, betas, flows, time, lower_bound=t_star)

    # -- entry ----------------------------------------------------------------

    def plan(self, scheme: str, caps: np.ndarray) -> Plan:
        """Plan one overlay; ``caps`` is its (d+1, d+1) capacity matrix."""
        cap = [[self.f(float(x)) for x in row] for row in np.asarray(caps)]
        return getattr(self, scheme)(cap)


def plan_batch(planner: Planner, scheme: str,
               caps: np.ndarray) -> dict:
    """Plan a (B, d+1, d+1) batch; arrays shaped like the program's result."""
    plans = [planner.plan(scheme, c) for c in caps]
    B, d = len(plans), planner.d
    parents = np.zeros((B, d + 1), np.int64)
    for b, p in enumerate(plans):
        for u, v in p.parent.items():
            parents[b, u] = v
    lbs = None
    if plans and plans[0].lower_bound is not None:
        lbs = np.array([float(p.lower_bound) for p in plans])
    return {"times": np.array([float(p.time) for p in plans]),
            "traffic": np.array([float(p.traffic) for p in plans]),
            "betas": np.array([[float(x) for x in p.betas] for p in plans]
                              ).reshape(B, d),
            "parents": parents, "lower_bounds": lbs}
