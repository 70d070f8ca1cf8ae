"""slot_walk engine parity on UPDATED graphs (interpret mode, CPU).

The interesting inputs are post-update slotted buffers: dead SENTINEL
slots after deletions, stale ``slot_rows`` on freed blocks, and moved
blocks after insert-driven growth — exactly the states the fused kernel's
run-rank trick must survive.  All paths are checked against the dense
numpy oracle and against the full-buffer jnp reference.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DiGraph, edgebatch, from_coo, traversal
from repro.core.digraph import COMPACT_MIN_SLOTS
from repro.io import synthetic
from repro.kernels.slot_walk import ops as sw_ops
from repro.kernels.slot_walk.ref import slot_walk_reference

STEPS = 4


def _make_graph(n=300, m=2400, seed=3):
    rng = np.random.default_rng(seed)
    src, dst = synthetic.uniform_edges(rng, n, m)
    return from_coo(src, dst, n=n), rng


def _oracle(g: DiGraph, steps: int) -> np.ndarray:
    nv = g.n_max_vertex() + 1
    return traversal.reverse_walk_dense_oracle(g.to_csr().to_dense(), steps)[:nv]


def _assert_walk_parity(g: DiGraph, steps: int = STEPS):
    nv = g.n_max_vertex() + 1
    exp = _oracle(g, steps)
    for backend, kw in (("pallas", {"interpret": True}), ("xla", {})):
        got = np.asarray(
            traversal.reverse_walk_slotted(
                g.dst, g.slot_rows, steps, nv, backend=backend, **kw
            )
        )
        np.testing.assert_allclose(got, exp, rtol=1e-4, err_msg=backend)
    ref = np.asarray(slot_walk_reference(g.dst, g.slot_rows, steps, nv))
    np.testing.assert_allclose(ref, exp, rtol=1e-4)


def test_parity_fresh_graph():
    c, _ = _make_graph()
    _assert_walk_parity(DiGraph.from_csr(c))


def test_parity_post_delete_dead_slots():
    """Heavy deletion leaves dead SENTINEL slots + stale slot_rows."""
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    g, dm = g.remove_edges(edgebatch.random_deletions(rng, c, c.m // 3))
    assert dm > 0 and g.live_fraction < 1.0
    _assert_walk_parity(g)


def test_parity_post_insert_block_growth():
    """Dense insert batch forces CP2AA block moves (stale freed blocks)."""
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    relayouts0 = g.stats.relayouts
    g, dm = g.add_edges(edgebatch.random_insertions(rng, c.n, c.m))
    assert dm > 0 and g.stats.relayouts > relayouts0
    _assert_walk_parity(g)


def test_parity_delete_then_insert_churn():
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    for _ in range(3):
        g, _ = g.remove_edges(edgebatch.random_deletions(rng, g.to_csr(), g.m // 4))
        g, _ = g.add_edges(edgebatch.random_insertions(rng, c.n, c.m // 5))
    _assert_walk_parity(g)


def test_edges_hi_prefix_matches_full_buffer():
    """Walking only the bump prefix must equal walking the whole buffer."""
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    g, _ = g.remove_edges(edgebatch.random_deletions(rng, c, c.m // 5))
    nv = g.n_max_vertex() + 1
    full = np.asarray(
        sw_ops.slot_walk(g.dst, g.slot_rows, STEPS, nv, backend="xla")
    )
    from repro.core import alloc

    hi = min(alloc.next_pow2(max(int(g.layout.bump), 1)), g.cap_e)
    pref = np.asarray(
        sw_ops.slot_walk(
            g.dst, g.slot_rows, STEPS, nv, edges_hi=hi, backend="xla"
        )
    )
    np.testing.assert_allclose(pref, full, rtol=1e-5)


def test_blocked_prefix_sum_path_parity():
    """Scatter-free block-interval path == segment-sum path on churned graphs."""
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    g, _ = g.remove_edges(edgebatch.random_deletions(rng, c, c.m // 3))
    g, _ = g.add_edges(edgebatch.random_insertions(rng, c.n, c.m // 4))
    nv = g.n_max_vertex() + 1
    starts = g.starts[:nv]
    has = starts >= 0
    lo = jnp.asarray(np.where(has, starts, 0).astype(np.int32))
    hi = jnp.asarray(np.where(has, starts + g.degrees[:nv], 0).astype(np.int32))
    blocked = np.asarray(
        sw_ops.slot_walk(
            g.dst, g.slot_rows, STEPS, nv,
            backend="xla", block_lo=lo, block_hi=hi,
        )
    )
    plain = np.asarray(
        sw_ops.slot_walk(g.dst, g.slot_rows, STEPS, nv, backend="xla")
    )
    np.testing.assert_allclose(blocked, plain, rtol=1e-4)
    np.testing.assert_allclose(blocked, _oracle(g, STEPS), rtol=1e-4)


def test_blocked_path_no_prefix_cancellation():
    """Large prefix totals must not leak float error into small row sums.

    Regression: a naive global f32 cumsum gave P[hi]-P[lo] errors of
    ~ulp(total) (≈0.6% rel on this flow); the two-level compensated
    prefix keeps integer-valued counts exact.
    """
    rng = np.random.default_rng(1)
    src, dst = synthetic.uniform_edges(rng, 1024, 10240)
    c = from_coo(src, dst, n=1024)
    g = DiGraph.from_csr(c)
    g, _ = g.remove_edges(edgebatch.random_deletions(rng, c, int(c.m * 0.85)))
    got = np.asarray(g.reverse_walk(6, auto_compact=False))
    exp = _oracle(g, 6)
    np.testing.assert_allclose(got, exp, rtol=1e-6)


def test_compaction_invariance():
    """Walk result (and edge sets) identical before/after compact()."""
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    g, _ = g.remove_edges(edgebatch.random_deletions(rng, c, c.m // 2))
    before_walk = np.asarray(g.reverse_walk(STEPS, auto_compact=False))
    before_sets = g.to_edge_sets()
    before_m = g.m
    reclaimed = g.compact()
    assert reclaimed >= 0
    assert g.m == before_m
    assert g.layout.bump <= g.cap_e
    after_walk = np.asarray(g.reverse_walk(STEPS, auto_compact=False))
    np.testing.assert_allclose(after_walk, before_walk, rtol=1e-4)
    assert g.to_edge_sets() == before_sets
    _assert_walk_parity(g)


def test_auto_compact_triggers_on_heavy_delete():
    c, rng = _make_graph(n=200, m=4000, seed=9)
    g = DiGraph.from_csr(c)
    g, _ = g.remove_edges(edgebatch.random_deletions(rng, c, int(c.m * 0.8)))
    assert g.layout.bump >= COMPACT_MIN_SLOTS
    assert g.live_fraction < 0.5
    exp = _oracle(g, STEPS)
    got = np.asarray(g.reverse_walk(STEPS))  # auto_compact=True default
    assert g.live_fraction >= 0.5  # compaction ran and repacked the prefix
    np.testing.assert_allclose(got, exp, rtol=1e-4)


def test_updates_after_compaction():
    """Compaction must leave a graph that still accepts updates."""
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    g, _ = g.remove_edges(edgebatch.random_deletions(rng, c, c.m // 2))
    g.compact()
    g, dm = g.add_edges(edgebatch.random_insertions(rng, c.n, c.m // 4))
    assert dm > 0
    _assert_walk_parity(g)


def test_to_csr_memoized_and_invalidated():
    c, rng = _make_graph()
    g = DiGraph.from_csr(c)
    a = g.to_csr()
    assert g.to_csr() is a  # cached
    g, _ = g.add_edges(edgebatch.random_insertions(rng, c.n, 10))
    b = g.to_csr()
    assert b is not a  # invalidated by mutation
    assert b.m == g.m


def test_empty_and_tiny_graphs():
    g = DiGraph.empty(4)
    nv = 4
    got = np.asarray(
        sw_ops.slot_walk(
            g.dst, g.slot_rows, 3, nv, backend="pallas", interpret=True
        )
    )
    np.testing.assert_allclose(got, 0.0)
    g, _ = g.add_edges(edgebatch.from_arrays([0, 1, 2], [1, 2, 3]))
    _assert_walk_parity(g, steps=3)


# ---------------------------------------------------------------------------
# sparse frontier step: seeded batches answer from the slots they hit
# ---------------------------------------------------------------------------
def _frontier_graph(kind: str):
    """A DiGraph for one frontier case, and the rng that built it."""
    rng = np.random.default_rng(11)
    n, m = 1500, 2250
    if kind == "hub":  # every other vertex points at vertex 0
        src = np.concatenate([np.arange(1, n), rng.integers(0, n, 300)])
        dst = np.concatenate([np.zeros(n - 1, np.int64), rng.integers(0, n, 300)])
        return DiGraph.from_csr(from_coo(src, dst, n=n)), rng
    src, dst = synthetic.uniform_edges(rng, n, m)
    if kind == "dies":  # vertex 0 has out-edges but no in-edges
        keep = dst != 0
        src, dst = np.concatenate([src[keep], [0, 0]]), np.concatenate(
            [dst[keep], [1, 2]]
        )
    c = from_coo(src, dst, n=n)
    g = DiGraph.from_csr(c)
    if kind == "churn":
        for _ in range(2):
            g, _ = g.remove_edges(
                edgebatch.random_deletions(rng, g.to_csr(), g.m // 4)
            )
            g, _ = g.add_edges(edgebatch.random_insertions(rng, n, m // 5))
    return g, rng


def _seed_rows(rng, nv: int, b: int, seeds) -> np.ndarray:
    """[b, nv] start rows: ``seeds`` ids per row with weights 1 and 2,
    the first id repeated so every row holds a duplicate seed."""
    v0 = np.zeros((b, nv), np.float32)
    for row in v0:
        ids = np.asarray(seeds) if not np.isscalar(seeds) else rng.integers(
            0, nv, seeds
        )
        ids = np.concatenate([ids, ids[:1]])
        np.add.at(row, ids, np.resize(np.float32([1.0, 2.0]), ids.shape[0]))
    return v0


def _oracle_rows(g: DiGraph, v0, steps: int, normalize: bool) -> np.ndarray:
    nv = v0.shape[1]
    a = g.to_csr().to_dense()[:nv, :nv].astype(np.float64)
    v = v0.astype(np.float64)
    for _ in range(steps):
        v = v @ a.T
        if normalize:
            v = v / np.maximum(v.max(axis=1, keepdims=True), 1.0)
    return v


@functools.partial(
    jax.jit, static_argnames=("steps", "nv", "edges_hi", "normalize", "engine",
                              "interpret")
)
def _dense_walk(dst, lo, hi, v0, steps, nv, *, edges_hi, normalize, engine,
                interpret):
    """The dense interval scan alone, as the walk program ran it before
    the sparse step existed."""
    step = sw_ops.make_blocked_step(
        sw_ops._prep_gidx(dst, nv, edges_hi), lo, hi, nv,
        engine=engine, interpret=interpret,
    )

    def body(v, _):
        nxt = step(v)
        if normalize:
            nxt = nxt / jnp.maximum(jnp.max(nxt, axis=1, keepdims=True), 1.0)
        return nxt, None

    return jax.lax.scan(body, v0, None, length=steps)[0]


#: case -> (graph, batch, seeds per row or the seed ids, steps, normalize,
#: whether the sparse form answers)
FRONTIER_CASES = {
    "fresh-b1": ("fresh", 1, 4, 3, False, True),
    "fresh-b4": ("fresh", 4, 4, 3, False, True),
    "churn-b4": ("churn", 4, 4, 3, False, True),
    "churn-b16": ("churn", 16, 3, 3, False, True),
    "normalize": ("churn", 4, 4, 3, True, True),
    "all-zero": ("fresh", 4, 0, 3, False, True),
    "dies": ("dies", 4, [0], 3, False, True),
    "hub-overflow": ("hub", 4, [0], 3, False, False),
    "all-ones": ("fresh", 2, None, 3, False, False),
}


@pytest.mark.parametrize("engine", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(FRONTIER_CASES))
def test_frontier_walk_matches_dense_and_oracle(case, engine):
    """The batched walk program against the dense interval scan and the
    numpy oracle, with the sparse-step counter it reports.  A frontier
    that overflows every width runs the dense scan: bit for bit."""
    kind, b, seeds, steps, normalize, sparse = FRONTIER_CASES[case]
    g, rng = _frontier_graph(kind)
    img = g.to_walk_image()
    nv = img.nv
    if seeds is None:
        v0 = np.ones((b, nv), np.float32)
    elif np.isscalar(seeds) and seeds == 0:
        v0 = np.zeros((b, nv), np.float32)
    else:
        v0 = _seed_rows(rng, nv, b, seeds)
    interpret = engine == "pallas"
    got = np.asarray(img.walk(steps, backend=engine, interpret=interpret,
                              visits0=v0, normalize=normalize))
    n_sparse = sw_ops.take_sparse_steps()
    assert int(n_sparse) == (steps if sparse else 0)
    lo, hi = img.device_blocks()
    dense = np.asarray(_dense_walk(
        img.dst, lo, hi, jnp.asarray(v0), steps, nv,
        edges_hi=img.edges_hi(), normalize=normalize, engine=engine,
        interpret=interpret,
    ))
    exp = _oracle_rows(g, v0, steps, normalize)
    scale = max(float(np.abs(exp).max()), 1.0)
    np.testing.assert_allclose(got, exp, rtol=1e-5, atol=1e-6 * scale)
    if sparse:
        np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-6 * scale)
    else:
        np.testing.assert_array_equal(got, dense)
    if kind == "dies":
        assert not got.any()  # the frontier died after one step


def test_frontier_step_seen_once_per_walk():
    """The sparse count is read once; a walk with no batched program
    leaves none behind."""
    g, rng = _frontier_graph("fresh")
    img = g.to_walk_image()
    img.walk(2, visits0=_seed_rows(rng, img.nv, 4, 4))
    assert int(sw_ops.take_sparse_steps()) == 2
    assert sw_ops.take_sparse_steps() is None
    img.walk(2)  # a single all-ones walk runs no batched program
    assert sw_ops.take_sparse_steps() is None
