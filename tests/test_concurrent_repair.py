"""Concurrent block-group reconstructions, as a node loss of a wide store
runs them: W threads, each with a coded store of its own, plan through
``plan_many(engine="jax")`` and repair through ``execute_plan`` with the
GF(2^8) kernel (interpreted on the CPU) as the store's matmul.

At RS(14,10)'s shape (n=14, k=10, d=13 at MSR, alpha = 4, M = 40, 64-byte
rows) the threads must give bit for bit what the same workers give one
after another; each plan must be the scalar oracle's, each product the
table field's, and a repaired node with 9 others must decode the block
group up to the code's chance.  Also pinned: the scoped ``enable_x64`` of
one thread leaves another's dtypes alone, the store's bytes are those it
held before each node's rows became arrays of their own, and a store
after loss and repair holds n * alpha rows."""
import hashlib
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.coding import GF8
from repro.core import CodeParams, OverlayNetwork, plan, plan_many, \
    plans_from_batch
from repro.kernels.ops import gf_matmul_numpy
from repro.obs import spans
from repro.storage.simulator import RlncSimulator

PARAMS = CodeParams.msr(n=14, k=10, d=13, M=40.0)
N, K, D, ALPHA, M = 14, 10, 13, 4, 40
ROW_BYTES = 64
WORKERS = 3
DRAWS = 5
#: relative gap allowed between the jit planner (float64) and the scalar
#: oracle: the two sum in different orders, so they agree to rounding
REL_TOL = 1e-9
#: the seeded store's bytes before ``distribute`` copied each node's rows
#: out of the one product (``_digest(RlncSimulator(PARAMS, 64, seed=11))``)
PARENT_DIGEST = \
    "574c929b233c25516ceea67158b1c93db6eee7bbbb2483d92f50d62fbd3ab683"


def _draws():
    """The fixed repairs: an overlay, the failed node and its 13 helpers."""
    rng = np.random.default_rng(20160517)
    out = []
    for _ in range(DRAWS):
        caps = rng.uniform(10.0, 120.0, (1, D + 1, D + 1))
        caps[:, np.arange(D + 1), np.arange(D + 1)] = 0.0
        failed = int(rng.integers(N))
        helpers = [int(x) for x in rng.permutation(
            [j for j in range(N) if j != failed])[:D]]
        out.append((caps, failed, helpers))
    return out


DRAWN = _draws()


def _digest(nodes) -> str:
    h = hashlib.sha256()
    for i in sorted(nodes):
        h.update(repr(i).encode())
        h.update(np.ascontiguousarray(nodes[i].vectors).tobytes())
        h.update(np.ascontiguousarray(nodes[i].payload).tobytes())
    return h.hexdigest()


class Worker:
    """One closed loop: a store of its own, built and repaired through the
    kernel, with every product it computed."""

    def __init__(self, index: int):
        self.index = index
        self.order = np.random.default_rng([7, index]).permutation(DRAWS)
        self.products = []
        self.plans = []
        self.store = None

    def matmul(self, a, b):
        out = gf_matmul_numpy(a, b)
        self.products.append((np.array(a), np.array(b), np.array(out)))
        return out

    def run(self, start: threading.Barrier = None):
        if start is not None:
            start.wait()
        self.store = RlncSimulator(PARAMS, block_bytes=ROW_BYTES,
                                   seed=100 + self.index, matmul=self.matmul)
        for i in self.order:
            caps, failed, helpers = DRAWN[i]
            res = plan_many(caps, PARAMS, "ftr", engine="jax")
            assert res.engine == "jax"
            self.plans.append((int(i), res))
            self.store.nodes.pop(failed)       # the node is lost
            self.store.execute_plan(plans_from_batch(res, PARAMS)[0],
                                    failed, helpers)
        return self


@pytest.fixture(scope="module")
def runs():
    """The workers in threads started at once (so their first compiles
    and first x64 scopes meet), then the same workers one after another."""
    concurrent = [Worker(w) for w in range(WORKERS)]
    start = threading.Barrier(WORKERS)
    errors = []

    def body(w):
        try:
            w.run(start)
        except BaseException as e:     # re-raised in the test's thread
            errors.append(e)

    threads = [threading.Thread(target=body, args=(w,)) for w in concurrent]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    in_turn = [Worker(w).run() for w in range(WORKERS)]
    return concurrent, in_turn


def test_concurrent_stores_equal_the_same_workers_in_turn(runs):
    for a, b in zip(*runs):
        assert sorted(a.store.nodes) == list(range(N))
        assert _digest(a.store.nodes) == _digest(b.store.nodes)
        assert np.array_equal(a.store.file_blocks, b.store.file_blocks)
        for (i, ra), (j, rb) in zip(a.plans, b.plans):
            assert i == j
            for f in ("times", "traffic", "betas", "parents",
                      "lower_bounds"):
                assert np.array_equal(getattr(ra, f), getattr(rb, f)), f
        assert len(a.products) == len(b.products)
        for pa, pb in zip(a.products, b.products):
            assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
        assert [(r.failed, r.rows_received, r.payload_bytes)
                for r in a.store.reconstructions] == \
            [(r.failed, r.rows_received, r.payload_bytes)
             for r in b.store.reconstructions]


def test_each_plan_matches_the_scalar_oracle(runs):
    oracle = {}
    for w in runs[0]:
        for i, res in w.plans:
            if i not in oracle:
                oracle[i] = plan(OverlayNetwork(DRAWN[i][0][0].tolist()),
                                 PARAMS, "ftr", engine="scalar")
            want = oracle[i]
            got = plans_from_batch(res, PARAMS)[0]
            assert got.parent == want.parent
            assert got.time == pytest.approx(want.time, rel=REL_TOL)
            np.testing.assert_allclose(got.betas, want.betas, rtol=REL_TOL,
                                       atol=REL_TOL)
    assert sorted(oracle) == list(range(DRAWS))


def test_every_product_matches_the_table_field(runs):
    for w in runs[0]:
        # the encode of the block group, then every repair's matmuls
        assert w.products[0][0].shape == (N * ALPHA, M)
        for a, b, out in w.products:
            assert np.array_equal(out, GF8.matmul(a, b))


def test_repaired_nodes_decode_the_block_group(runs):
    """A repaired node and 9 others, drawn from a seed, decode the file;
    random linear coding over GF(2^8) leaves a subset a row short with
    probability about 1/255, so one subset may fall short by one row, as
    the chip benchmark's check allows."""
    rng = np.random.default_rng(3)
    short = []
    subsets = 0
    for w in runs[0]:
        sim = w.store
        for node in sorted({r.failed for r in sim.reconstructions}):
            others = rng.choice([j for j in range(N) if j != node], K - 1,
                                replace=False)
            subset = [sim.nodes[j] for j in [node, *others]]
            subsets += 1
            rank = GF8.rank(np.concatenate([s.vectors for s in subset]))
            if rank < M:
                short.append(M - rank)
                continue
            assert np.array_equal(sim.rl.reconstruct(subset, M),
                                  sim.file_blocks)
    assert subsets >= WORKERS
    assert len(short) <= 1 and all(s == 1 for s in short), short


def test_counters_and_host_bytes_of_each_store(runs):
    for w in runs[0]:
        sim = w.store
        recs = sim.reconstructions
        assert [r.failed for r in recs] == [DRAWN[i][1] for i in w.order]
        assert all(r.start_s < r.end_s for r in recs)
        assert all(a.end_s <= b.start_s for a, b in zip(recs, recs[1:]))
        # the repairs' products, after the encode: a payload operand is
        # ROW_BYTES wide, a coding-vector operand M wide; the last is the
        # newcomer's regenerate, alpha combinations of every row received
        payload_ops = [(a, b) for a, b, _ in w.products[1:]
                       if b.shape[1] == ROW_BYTES]
        assert sum(b.nbytes for _, b in payload_ops) == \
            sum(r.payload_bytes for r in recs)
        assert w.products[-1][0].shape == (ALPHA, recs[-1].rows_received)
        assert all(r.rows_received >= ALPHA for r in recs)
        # loss and repair leave n * alpha rows: no view keeps the encode's
        # product, no repaired node adds rows
        assert sim.host_bytes() == N * ALPHA * (M + ROW_BYTES)


def test_distribute_keeps_the_bytes_and_gives_each_node_its_own_rows():
    sim = RlncSimulator(PARAMS, block_bytes=ROW_BYTES, seed=11)
    assert _digest(sim.nodes) == PARENT_DIGEST
    arrays = [a for b in sim.nodes.values() for a in (b.vectors, b.payload)]
    assert all(a.base is None for a in arrays)
    assert sim.host_bytes() == N * ALPHA * (M + ROW_BYTES)
    # a view of one product is counted whole: 14 nodes' slices of it hold
    # the product, and the dropped node's rows are not freed
    whole = np.concatenate([sim.nodes[i].payload for i in range(N)])
    for i in range(N):
        sim.nodes[i].payload = whole[i * ALPHA:(i + 1) * ALPHA]
    sim.nodes.pop(0)
    assert sim.host_bytes() == (N - 1) * ALPHA * M + N * ALPHA * ROW_BYTES


def test_an_x64_scope_in_one_thread_leaves_another_threads_dtype():
    inside, done = threading.Event(), threading.Event()
    seen = {}

    def scoped():
        with jax.enable_x64():
            seen["inside"] = jnp.asarray(1.0).dtype
            inside.set()
            done.wait(30)
            seen["inside_after"] = jnp.zeros(2).dtype

    t = threading.Thread(target=scoped)
    t.start()
    assert inside.wait(30)
    outside = jnp.asarray(1.0).dtype, jnp.zeros(2).dtype
    done.set()
    t.join()
    assert outside == (jnp.float32, jnp.float32)
    assert seen == {"inside": jnp.float64, "inside_after": jnp.float64}


def test_first_spans_of_several_threads_at_once(monkeypatch):
    monkeypatch.setattr(spans, "_annotation", None)
    start = threading.Barrier(8)
    entered = []

    def body():
        start.wait()
        with spans.span("store.node", bytes=1) as s:
            s.set_metadata(rows=1)
            entered.append(type(s))

    threads = [threading.Thread(target=body) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert entered == [jax.profiler.TraceAnnotation] * 8
