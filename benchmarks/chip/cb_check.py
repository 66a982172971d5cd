"""The comparison that decides ``correct``: what the window produced against
the benchmark's own reference.

- Plans: a sample, drawn from the seed, of the distinct plans that each
  worker's ``plan_many`` calls returned in the window (workers replay the
  same draws, and each worker's plans count apart), each against the
  plain reference planner in float64 (``cb_planref``).  ``plan_gap`` is
  the widest relative gap
  |a - b| / (1 + |b|) over times, traffic, betas and lower bounds, where a
  plan on another tree, or with another set of finite values, counts as a
  gap of 1.  ``invalid_plans`` counts, over every distinct plan of every
  worker, those that fail against their own overlay (not a tree rooted at
  the newcomer, MDS condition sigma_1(beta) >= M/k broken, time
  understated, traffic not the sum of the flows).
- Kernel: ``kernel_bytes_wrong`` counts the bytes, in columns drawn from the
  seed, that a GF(2^8) matmul of the window returned and the table
  arithmetic of ``cb_gf`` does not give for the same operands.
- Store, after the window, in every worker's store: ``store_rows_bad``
  counts stored rows whose payload is not their coding vector times the
  worker's block group (made again from the seed), checked on every byte by
  GF sums of columns, and every row of a node that is missing;
  ``subset_rank_deficit`` is the largest M - rank of the coding vectors of
  a k-node subset: one subset of the worker's store, drawn from the seed,
  for every node a worker repaired in the window, holding that node; a
  missing node adds no row.  A random linear code over GF(2^8) leaves a
  k-subset a row short by chance (about 1 subset in 255, the encode
  alone); a repair that loses a helper's contribution, or half its rows,
  leaves it tens of rows short.

Each limit sits between the readings it was set from (``PERF.md``).
"""
from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np

import cb_gf
import cb_planref
from cb_traffic import Traffic, data_file

#: plan_gap: sound runs of the program read at most ~1e-14 and the float32
#: control at least ~1.7e-8; subset_rank_deficit: sound runs read at most 1
#: row and the faults it catches 128 or more (PERF.md); the counts are exact
#: comparisons
LIMITS = {
    "plan_gap": 1e-10,
    "invalid_plans": 0,
    "kernel_bytes_wrong": 0,
    "store_rows_bad": 0,
    "subset_rank_deficit": 16,
}
#: distinct plans of each scheme compared with the reference
CHECK_PLANS = 24
#: random column masks, besides all columns, whose GF row sums check a row
STORE_MASKS = 2

Number = Tuple[str, float, float]


def _gap(a, b) -> float:
    a = np.atleast_1d(np.asarray(a, float))
    b = np.atleast_1d(np.asarray(b, float))
    fin = np.isfinite(b)
    if a.shape != b.shape or not np.array_equal(fin, np.isfinite(a)) or \
            not np.array_equal(a[~fin], b[~fin]):
        return 1.0
    if not fin.any():
        return 0.0
    return float((np.abs(a[fin] - b[fin]) / (1.0 + np.abs(b[fin]))).max())


def valid_plan(parents, betas, time, traffic, caps, k, d, M) -> bool:
    """The plan against its own overlay, with nothing of the reference."""
    alpha = M / k
    parent = {u: int(parents[u]) for u in range(1, d + 1)}
    for u in range(1, d + 1):
        seen, x = set(), u
        while x != 0:
            if x in seen or not 1 <= x <= d:
                return False
            seen.add(x)
            x = parent[x]
    betas = [float(b) for b in betas]
    if not all(math.isfinite(b) and b >= 0 for b in betas):
        return False
    if sum(sorted(betas)[:d - k + 1]) < alpha * (1 - 1e-9):
        return False
    flows = cb_planref.tree_flows(parent, betas, alpha)
    t = max(f / caps[u][v] for (u, v), f in flows.items())
    if not t <= float(time) * (1 + 1e-9):
        return False
    total = sum(flows.values())
    return abs(total - float(traffic)) <= 1e-9 * (1 + abs(total))


def plan_numbers(traffic: Traffic, seed: int) -> Tuple[List[Number], int]:
    cfg = traffic.config
    k, d, M = cfg["k"], cfg["d"], float(cfg["M"])
    lanes = {}
    seen = set()
    invalid = 0
    for ri, (scheme, caps, res) in enumerate(traffic.plan_records):
        worker = traffic.worker_of["plan_records"][ri]
        for b in range(caps.shape[0]):
            # every worker replays the same draws: each plans them anew
            key = (worker, scheme, caps[b].tobytes())
            if key in seen:
                continue
            seen.add(key)
            lanes.setdefault(scheme, []).append((ri, b))
            invalid += not valid_plan(res.parents[b], res.betas[b],
                                      res.times[b], res.traffic[b], caps[b],
                                      k, d, M)
    rng = np.random.default_rng([seed, 5])
    ref = cb_planref.Planner(k, d, M)
    gap, far = 0.0, 0
    for scheme in sorted(lanes):
        pool = lanes[scheme]
        pick = rng.choice(len(pool), min(len(pool), CHECK_PLANS),
                          replace=False)
        for i in sorted(pick):
            ri, b = pool[i]
            _, caps, res = traffic.plan_records[ri]
            want = ref.plan(scheme, caps[b])
            if any(int(res.parents[b, u]) != want.parent[u]
                   for u in range(1, d + 1)):
                g = 1.0
            else:
                g = max(_gap(res.times[b], float(want.time)),
                        _gap(res.traffic[b], float(want.traffic)),
                        _gap(res.betas[b], [float(x) for x in want.betas]))
                if (res.lower_bounds is None) != (want.lower_bound is None):
                    g = 1.0
                elif want.lower_bound is not None:
                    g = max(g, _gap(res.lower_bounds[b],
                                    float(want.lower_bound)))
            gap = max(gap, g)
            far += g > LIMITS["plan_gap"]
    return ([("plan_gap", gap, LIMITS["plan_gap"]),
             ("invalid_plans", invalid, LIMITS["invalid_plans"])],
            far + invalid)


def kernel_numbers(traffic: Traffic) -> Tuple[List[Number], int]:
    wrong_bytes = wrong_calls = 0
    for a, b_cols, out_cols in traffic.mm_samples:
        w = int(np.count_nonzero(cb_gf.matmul(a, b_cols) != out_cols))
        wrong_bytes += w
        wrong_calls += w > 0
    return ([("kernel_bytes_wrong", wrong_bytes,
              LIMITS["kernel_bytes_wrong"])], wrong_calls)


def store_numbers(traffic: Traffic, seed: int) -> Tuple[List[Number], int]:
    cfg = traffic.config
    n, k, alpha, M, cell = (cfg["n"], cfg["k"], cfg["alpha"], cfg["M"],
                            cfg["cell_bytes"])
    rng = np.random.default_rng([seed, 6])
    masks = [None] + [cb_gf.column_mask(rng, cell)
                      for _ in range(STORE_MASKS)]
    bad = 0
    for worker in traffic.workers:
        file = data_file(seed, M, cell, worker.index)
        sums = [cb_gf.row_sums(file, m) for m in masks]
        del file
        nodes = worker.store.nodes
        for i in range(n):
            node = nodes.get(i)
            if node is None or node.vectors.shape != (alpha, M) or \
                    node.payload.shape != (alpha, cell):
                bad += alpha
                continue
            row_bad = np.zeros(alpha, bool)
            for m, s in zip(masks, sums):
                row_bad |= cb_gf.row_sums(node.payload, m) != \
                    cb_gf.matmul(node.vectors, s[:, None])[:, 0]
            bad += int(row_bad.sum())
    deficit, short = 0, 0
    for w, node in sorted(set(zip(traffic.worker_of["repaired"],
                                  traffic.repaired))):
        nodes = traffic.workers[w].store.nodes
        others = [i for i in range(n) if i != node]
        subset = [node] + [int(x) for x in
                           rng.choice(others, k - 1, replace=False)]
        rows = [nodes[i].vectors for i in subset
                if i in nodes and nodes[i].vectors.shape[1:] == (M,)]
        gap = M - (cb_gf.rank(np.concatenate(rows)) if rows else 0)
        deficit = max(deficit, gap)
        short += gap > LIMITS["subset_rank_deficit"]
    return ([("store_rows_bad", bad, LIMITS["store_rows_bad"]),
             ("subset_rank_deficit", deficit,
              LIMITS["subset_rank_deficit"])], bad + short)


def check(traffic: Traffic, seed: int) -> Tuple[List[Number], int]:
    """(numbers with their limits, answers found wrong)."""
    numbers, wrong = plan_numbers(traffic, seed)
    if traffic.mix["repair"]:
        for part in (kernel_numbers(traffic), store_numbers(traffic, seed)):
            numbers += part[0]
            wrong += part[1]
    return numbers, wrong


def within(numbers: List[Number]) -> bool:
    return all(value <= limit for _, value, limit in numbers)
