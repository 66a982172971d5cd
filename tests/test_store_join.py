"""The coded store's walk joins each pool once, where a GF matmul reads it.

``RlncSimulator.execute_plan`` keeps a node's pool as a list of parts and
joins it only at a relaying node and at the newcomer.  The walk it replaced
joined every child's blocks onto the prefix, then the pool again inside
``RLNC.relay``; it stays here as the oracle: both walks must leave every
node's coding vectors and payload byte-identical, with the same GF calls.
"""
import math
import random

import numpy as np
import pytest

from repro.coding import GF8, RLNC, CodedBlocks
from repro.core import CodeParams, OverlayNetwork, plan
from repro.storage.simulator import RlncSimulator

PARAMS = CodeParams.msr(n=6, k=3, d=5, M=12.0)
FAILED, PROVIDERS = 0, [1, 2, 3, 4, 5]

# scheme and overlay seed of each case, and what its tree does
CASES = {
    "star": ("star", 0),            # d parts joined once at the newcomer
    "ftr-relay": ("ftr", 9),        # two relaying nodes
    "ftr-forward": ("ftr", 21),     # interior pools equal to their flow
    "ftr-chain": ("ftr", 15),       # one child of the newcomer, relaying
    "rctree": ("rctree", 0),        # flows capped below the pools
}


def _plan(case):
    scheme, seed = CASES[case]
    rng = random.Random(seed)
    d = PARAMS.d
    cap = [[rng.uniform(10, 120) if u != v else 0.0 for v in range(d + 1)]
           for u in range(d + 1)]
    return plan(OverlayNetwork(cap), PARAMS, scheme, engine="scalar")


def _children(pl):
    children = {}
    for u, p in pl.parent.items():
        children.setdefault(p, []).append(u)
    return children


def _ceil(x):
    return int(math.ceil(x - 1e-9))


def chained_walk(sim, pl, failed, provider_ids):
    """The walk before pools were lists of parts: each child's blocks are
    concatenated onto the prefix, the pool once more with the node's own,
    and again inside ``RLNC.relay``."""
    alpha = int(round(sim.params.alpha))
    idmap = dict(enumerate(provider_ids, start=1))
    children = _children(pl)

    def produce(u):
        recv = None
        for ch in children.get(u, []):
            part = produce(ch)
            recv = part if recv is None else recv.concat(part)
        send_quota = _ceil(pl.flows[(u, pl.parent[u])])
        own = sim.rl.encode(sim.nodes[idmap[u]], _ceil(pl.betas[u - 1]),
                            sim.np_rng)
        if recv is None:
            out = own
        else:
            pool = recv.concat(own)
            out = (sim.rl.relay(recv, own, send_quota, sim.np_rng)
                   if pool.num > send_quota else pool)
        if out.num > send_quota:
            out = CodedBlocks(out.vectors[:send_quota],
                              out.payload[:send_quota])
        return out

    received = None
    for r in children.get(0, []):
        part = produce(r)
        received = part if received is None else received.concat(part)
    sim.nodes[failed] = sim.rl.regenerate(received, alpha, sim.np_rng)


def reckoned(pl):
    """(rows of each join the walk needs, in walk order; relaying nodes;
    forwarding nodes): one join per relaying node's pool, and one of the
    newcomer's pool where it has several parts."""
    children = _children(pl)
    joins, relays, forwards = [], [], []

    def parts(u):
        pool = [r for ch in children.get(u, []) for r in parts(ch)]
        quota, own = _ceil(pl.flows[(u, pl.parent[u])]), _ceil(pl.betas[u - 1])
        if not pool:
            return [min(own, quota)]
        pool.append(own)
        if sum(pool) <= quota:
            forwards.append(u)
            return pool
        relays.append(u)
        joins.append(sum(pool))
        return [quota]

    received = [r for ch in children[0] for r in parts(ch)]
    if len(received) > 1:
        joins.append(sum(received))
    return joins, relays, forwards


def _store(seed, shapes):
    sim = RlncSimulator(PARAMS, block_bytes=8, seed=seed)

    def matmul(a, b):
        shapes.append((a.shape[0], a.shape[1], b.shape[1]))
        return GF8.matmul(a, b)

    sim.rl = RLNC(GF8, matmul=matmul)
    return sim


@pytest.fixture
def joined(monkeypatch):
    """Rows of every join that copies (of two parts or more), in order."""
    rows = []
    join = CodedBlocks.join

    def recording(parts):
        out = join(parts)
        if len(parts) > 1:
            rows.append(out.num)
        return out

    monkeypatch.setattr(CodedBlocks, "join", staticmethod(recording))
    return rows


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("case", list(CASES))
def test_walk_matches_chained_walk(case, seed):
    pl = _plan(case)
    _, relays, forwards = reckoned(pl)
    if case == "star":
        assert all(p == 0 for p in pl.parent.values())
    elif case == "ftr-forward":
        assert forwards
    else:
        assert relays
    want_shapes, got_shapes = [], []
    want, got = _store(seed, want_shapes), _store(seed, got_shapes)
    chained_walk(want, pl, FAILED, PROVIDERS)
    got.execute_plan(pl, FAILED, PROVIDERS)
    assert got_shapes == want_shapes
    assert sorted(got.nodes) == sorted(want.nodes)
    for i in want.nodes:
        assert got.nodes[i].vectors.dtype == want.nodes[i].vectors.dtype
        assert np.array_equal(got.nodes[i].vectors, want.nodes[i].vectors)
        assert np.array_equal(got.nodes[i].payload, want.nodes[i].payload)


@pytest.mark.parametrize("seed", [3, 11])
@pytest.mark.parametrize("case", list(CASES))
def test_walk_joins_the_reckoned_rows(case, seed, joined):
    pl = _plan(case)
    want, relays, _ = reckoned(pl)
    shapes = []
    sim = _store(seed, shapes)
    joined.clear()                     # the store's encode joins nothing
    sim.execute_plan(pl, FAILED, PROVIDERS)
    assert joined == want
    # once per relaying node, and once at the newcomer unless its one
    # child relays
    assert len(joined) == len(relays) + (case != "ftr-chain")
    if case == "star":
        assert joined == [sum(_ceil(b) for b in pl.betas)]
    # each join is the pool of the two GF calls that read it
    k_dims = [k for _, k, _ in shapes]
    assert all(k_dims.count(r) >= 2 for r in joined)
    # the chained walk copied at least as many rows
    chained = []
    joined_rows = list(joined)
    joined.clear()
    chained_walk(_store(seed, chained), pl, FAILED, PROVIDERS)
    assert sum(joined) >= sum(joined_rows)
    if case != "star":
        assert sum(joined) > sum(joined_rows)
