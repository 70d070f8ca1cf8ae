"""Batch-update engine tests: UpdatePlan, slot_update parity, apply().

Covers the three layers of DESIGN.md §9: host planning (canonical op
stream, runs, cache), the fused device merge (Pallas-interpret vs XLA vs
the numpy oracle), and the mixed-batch ``apply`` entry point on every
representation.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (
    REPRESENTATIONS,
    DiGraph,
    edgebatch,
    from_coo,
    traversal,
    updates,
    util,
)
from repro.io import synthetic
from repro.kernels.slot_update import ops as su_ops
from repro.kernels.slot_update.ref import merge_rows_reference

SENT = util.SENTINEL
REPS = list(REPRESENTATIONS.items())


# ---------------------------------------------------------------------------
# host planning layer
# ---------------------------------------------------------------------------
def test_plan_canonicalization_insert_wins():
    ins = edgebatch.from_arrays([3, 3, 1], [5, 9, 2], [1.0, 2.0, 3.0])
    dele = edgebatch.from_arrays([3, 2, 3], [9, 4, 5])  # (3,9),(3,5) clash
    p = updates.plan_update(inserts=ins, deletes=dele)
    ops = set(zip(p.q_src.tolist(), p.q_dst.tolist(), p.q_del.tolist()))
    assert (3, 9, False) in ops and (3, 5, False) in ops  # inserts won
    assert (2, 4, True) in ops
    assert p.n_del == 1 and p.n_ins == 3
    # ascending (src, dst), one op per key
    keys = list(zip(p.q_src.tolist(), p.q_dst.tolist()))
    assert keys == sorted(keys) and len(keys) == len(set(keys))


def test_plan_runs_and_tiles():
    ins = edgebatch.from_arrays([7, 7, 7, 0], [1, 2, 3, 9])
    p = updates.plan_update(inserts=ins)
    assert p.rows.tolist() == [0, 7]
    assert p.run_count.tolist() == [1, 3]
    assert p.ins_count.tolist() == [1, 3]
    assert p.run_width == 4
    bd, bw, bl = p.run_tiles(np.arange(2), 4, a_pad=4)
    assert bd.shape == (4, 4)
    assert bd[1, :3].tolist() == [1, 2, 3]
    assert (bd[0, 1:] == SENT).all()
    assert (bd[2:] == SENT).all()  # pad rows
    assert bl.sum() == 0
    # a subset selection only materializes its own rows
    bd7, _, _ = p.run_tiles(np.array([1]), 4)
    assert bd7.shape == (1, 4) and bd7[0, :3].tolist() == [1, 2, 3]


def test_plan_enforces_one_op_per_key():
    """dedup=False batches with duplicate keys must not corrupt a plan."""
    ins = edgebatch.from_arrays([0, 0], [5, 5], [1.0, 2.0], dedup=False)
    p = updates.plan_update(inserts=ins)
    assert p.n_ops == 1 and p.q_wgt[0] == pytest.approx(1.0)  # first wins
    g = DiGraph.from_csr(from_coo([0], [1], n=2))
    g, dm = g.apply(p)
    assert dm == 1 and g.m == 2
    row = g.edges_of(0)
    assert row.tolist() == [1, 5] and (np.diff(row) > 0).all()


def test_plan_cache_identity():
    ins = edgebatch.from_arrays([1], [2])
    p1 = updates.plan_update(inserts=ins)
    assert updates.plan_update(inserts=ins) is p1
    # a different batch object builds a fresh plan
    ins2 = edgebatch.from_arrays([1], [2])
    assert updates.plan_update(inserts=ins2) is not p1


def test_empty_plan():
    p = updates.plan_update()
    assert p.n_ops == 0 and p.n_rows == 0
    for name, cls in REPS:
        g = cls.from_csr(from_coo([0], [1], n=4))
        g2, dm = g.apply(p)
        assert dm == 0


# ---------------------------------------------------------------------------
# EdgeBatch validation (satellite)
# ---------------------------------------------------------------------------
def test_edgebatch_rejects_negative_ids():
    with pytest.raises(ValueError, match="negative"):
        edgebatch.from_arrays([-1], [2])
    with pytest.raises(ValueError, match="negative"):
        edgebatch.from_arrays([1], [-2])


def test_edgebatch_rejects_overflow_and_bad_dtypes():
    with pytest.raises(ValueError, match="overflow"):
        edgebatch.from_arrays([2**31 - 1], [0])
    with pytest.raises(ValueError, match="non-integral"):
        edgebatch.from_arrays([1.5], [0])
    with pytest.raises(TypeError):
        edgebatch.from_arrays(["a"], [0])
    with pytest.raises(ValueError, match="mismatch"):
        edgebatch.from_arrays([1, 2], [0])


def test_edgebatch_accepts_integral_floats_and_int64():
    b = edgebatch.from_arrays(np.array([1.0, 2.0]), np.array([3, 4], np.int64))
    assert b.n == 2 and b.src.dtype == jnp.int32


def test_dedup_arrays_keep_first_last():
    s = np.array([1, 1, 0], np.int32)
    d = np.array([2, 2, 5], np.int32)
    w = np.array([10.0, 20.0, 30.0], np.float32)
    s1, d1, w1 = edgebatch.dedup_arrays(s, d, w, keep="first")
    assert w1.tolist() == [30.0, 10.0]
    s2, d2, w2 = edgebatch.dedup_arrays(s, d, w, keep="last")
    assert w2.tolist() == [30.0, 20.0]


# ---------------------------------------------------------------------------
# device merge parity: xla == pallas(interpret) == numpy oracle
# ---------------------------------------------------------------------------
def _random_merge_case(rng, a=8, w=64, k=8):
    d_rows = np.full((a, w), SENT, np.int32)
    w_rows = np.zeros((a, w), np.float32)
    degs = rng.integers(0, w // 2, a).astype(np.int32)
    for i in range(a):
        vals = np.sort(rng.choice(500, degs[i], replace=False)).astype(np.int32)
        d_rows[i, : degs[i]] = vals
        w_rows[i, : degs[i]] = rng.random(degs[i])
    b_d = np.full((a, k), SENT, np.int32)
    b_w = np.zeros((a, k), np.float32)
    b_l = np.zeros((a, k), np.int32)
    for i in range(a):
        kk = int(rng.integers(0, k + 1))
        pool = np.concatenate([d_rows[i, : degs[i]], rng.choice(500, 10)])
        vals = np.unique(rng.choice(pool, kk)) if kk else np.empty(0, np.int64)
        b_d[i, : len(vals)] = vals
        b_w[i, : len(vals)] = rng.random(len(vals))
        b_l[i, : len(vals)] = rng.integers(0, 2, len(vals))
    return d_rows, w_rows, degs, b_d, b_w, b_l


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_merge_rows_backend_parity(seed):
    rng = np.random.default_rng(seed)
    case = _random_merge_case(rng)
    exp_d, exp_w, exp_c = merge_rows_reference(*case)
    args = tuple(jnp.asarray(x) for x in case)
    for backend, kw in (("xla", {}), ("pallas", {"interpret": True})):
        od, ow, cnt = su_ops.merge_rows(*args, backend=backend, **kw)
        np.testing.assert_array_equal(np.asarray(od), exp_d, err_msg=backend)
        np.testing.assert_allclose(np.asarray(ow), exp_w, rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(cnt), exp_c)


@pytest.mark.parametrize("a,w,k", [(8, 256, 8), (5, 1024, 64), (3, 2048, 8)])
def test_merge_rows_pallas_chunked_rows_exact_ids(a, w, k):
    """Rows of several 128-lane chunks (band-limited placement across chunk
    seams) merge exactly, ids above 2**24 included; rows wider than the
    kernel's MAX_WIDTH take the XLA formulation."""
    rng = np.random.default_rng(w + k)
    d_rows = np.full((a, w), SENT, np.int32)
    w_rows = np.zeros((a, w), np.float32)
    degs = rng.integers(0, w - k + 1, a).astype(np.int32)
    degs[0] = w - k  # a full row: survivors shift across every seam
    b_d = np.full((a, k), SENT, np.int32)
    b_w = np.zeros((a, k), np.float32)
    b_l = np.zeros((a, k), np.int32)
    for i in range(a):
        vals = np.unique(rng.integers(0, 1 << 30, 2 * int(degs[i]) + 2))
        vals = np.sort(rng.permutation(vals)[: degs[i]]).astype(np.int32)
        degs[i] = vals.shape[0]
        d_rows[i, : degs[i]] = vals
        w_rows[i, : degs[i]] = rng.random(degs[i])
        pool = np.concatenate([vals, rng.integers(0, 1 << 30, k)])
        ops = np.unique(rng.choice(pool, k))[:k]
        b_d[i, : ops.shape[0]] = ops
        b_w[i, : ops.shape[0]] = rng.random(ops.shape[0])
        b_l[i, : ops.shape[0]] = rng.integers(0, 2, ops.shape[0])
    case = (d_rows, w_rows, degs, b_d, b_w, b_l)
    exp_d, exp_w, exp_c = merge_rows_reference(*case)
    od, ow, cnt = su_ops.merge_rows(
        *(jnp.asarray(x) for x in case), backend="pallas", interpret=True
    )
    np.testing.assert_array_equal(np.asarray(od), exp_d)
    np.testing.assert_allclose(np.asarray(ow), exp_w, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(cnt), exp_c)


# ---------------------------------------------------------------------------
# mixed-batch apply on every representation
# ---------------------------------------------------------------------------
def _apply_oracle(sets, plan):
    for s, d, dl in zip(plan.q_src, plan.q_dst, plan.q_del):
        while len(sets) <= int(s) or len(sets) <= int(d):
            sets.append(set())
        if dl:
            sets[int(s)].discard(int(d))
        else:
            sets[int(s)].add(int(d))
    return sets


@pytest.mark.parametrize("name,cls", REPS)
def test_apply_mixed_batch_vs_oracle(name, cls):
    rng = np.random.default_rng(23)
    n = 48
    src, dst = synthetic.uniform_edges(rng, n, 300)
    c = from_coo(src, dst, n=n)
    g = cls.from_csr(c)
    sets = [set(x) for x in c.to_edge_sets()]
    for _ in range(4):
        ins = edgebatch.random_insertions(rng, n, 25)
        dele = edgebatch.random_deletions(rng, g.to_csr(), 20)
        plan = updates.plan_update(inserts=ins, deletes=dele)
        g, dm = g.apply(plan)
        sets = _apply_oracle(sets, plan)
        got = g.to_edge_sets()
        while len(got) < len(sets):
            got.append(set())
        assert got[: len(sets)] == sets, f"{name}: mixed apply diverged"


@pytest.mark.parametrize("name,cls", REPS)
def test_apply_delete_then_reinsert_same_key(name, cls):
    """A key in both halves of one mixed batch ends up present (upsert)."""
    c = from_coo([0, 0], [1, 2], [1.0, 2.0], n=3)
    g = cls.from_csr(c)
    plan = updates.plan_update(
        inserts=edgebatch.from_arrays([0], [1], [9.0]),
        deletes=edgebatch.from_arrays([0, 0], [1, 2]),
    )
    g, dm = g.apply(plan)
    cc = g.to_csr()
    assert g.to_edge_sets()[0] == {1}, f"{name}: insert did not win"
    i0, i1 = int(np.asarray(cc.offsets)[0]), int(np.asarray(cc.offsets)[1])
    ww = dict(
        zip(np.asarray(cc.dst)[i0:i1].tolist(), np.asarray(cc.wgt)[i0:i1].tolist())
    )
    assert ww[1] == pytest.approx(9.0), f"{name}: weight not upserted"


@pytest.mark.parametrize("name,cls", REPS)
def test_walk_after_mixed_apply(name, cls):
    rng = np.random.default_rng(31)
    n = 40
    src, dst = synthetic.uniform_edges(rng, n, 240)
    c = from_coo(src, dst, n=n)
    g = cls.from_csr(c)
    plan = updates.plan_update(
        inserts=edgebatch.random_insertions(rng, n, 30),
        deletes=edgebatch.random_deletions(rng, c, 25),
    )
    g, _ = g.apply(plan)
    cc = g.to_csr()
    exp = traversal.reverse_walk_dense_oracle(cc.to_dense(), 4)
    got = np.asarray(g.reverse_walk(4))[: cc.n]
    np.testing.assert_allclose(got, exp, rtol=1e-5)


def test_digraph_apply_grow_path_mixed():
    """Mixed plan whose inserts force CP2AA block moves inside apply."""
    rng = np.random.default_rng(5)
    n = 32
    src, dst = synthetic.uniform_edges(rng, n, 150)
    c = from_coo(src, dst, n=n)
    g = DiGraph.from_csr(c)
    relayouts0 = g.stats.relayouts
    # a hub row gains many edges (growth) while others lose some
    ins = edgebatch.from_arrays(np.zeros(40, np.int64), 100 + np.arange(40))
    dele = edgebatch.random_deletions(rng, c, 30)
    g, dm = g.apply(updates.plan_update(inserts=ins, deletes=dele))
    assert g.stats.relayouts > relayouts0
    assert g.degree(0) >= 40
    row = g.edges_of(0)
    assert (np.diff(row) > 0).all()  # ascending invariant held
    # delete-only rows beyond cap_v are filtered, not fatal
    g, dm2 = g.apply(
        updates.plan_update(deletes=edgebatch.from_arrays([10 * n], [1]))
    )
    assert dm2 == 0


def test_edgebatch_rejects_wgt_length_mismatch():
    with pytest.raises(ValueError, match="wgt length"):
        edgebatch.from_arrays([0, 1], [2, 3], [9.0, 8.0, 7.0])
    with pytest.raises(ValueError, match="wgt length"):
        edgebatch.from_arrays([0, 1], [2, 3], [9.0])


def test_digraph_scatter_writeback_path(monkeypatch):
    """Force the per-group scatter write-back (the TPU/big-arena path)."""
    monkeypatch.setattr(su_ops, "REBUILD_MAX_CAP", 0)
    rng = np.random.default_rng(41)
    n = 48
    src, dst = synthetic.uniform_edges(rng, n, 300)
    c = from_coo(src, dst, n=n)
    g = DiGraph.from_csr(c)
    sets = [set(x) for x in c.to_edge_sets()]
    for _ in range(3):
        # hub growth + random churn exercises block moves in scatter mode
        ins = edgebatch.from_arrays(
            np.concatenate([np.zeros(20, np.int64), rng.integers(0, n, 15)]),
            np.concatenate([200 + rng.integers(0, 500, 20), rng.integers(0, n, 15)]),
        )
        dele = edgebatch.random_deletions(rng, g.to_csr(), 20)
        plan = updates.plan_update(inserts=ins, deletes=dele)
        g, _ = g.apply(plan)
        sets = _apply_oracle(sets, plan)
        got = g.to_edge_sets()
        while len(got) < len(sets):
            got.append(set())
        assert got[: len(sets)] == sets, "scatter path diverged"
    # arena invariants: packed ascending rows, SENTINEL tails
    dstbuf = np.asarray(g.dst)
    for u in range(g.cap_v):
        cp, s, d_ = int(g.capacities[u]), int(g.starts[u]), int(g.degrees[u])
        if cp == 0:
            assert d_ == 0
            continue
        row = dstbuf[s : s + cp]
        live = row[row != SENT]
        assert live.shape[0] == d_
        assert (row[d_:] == SENT).all()
    assert g.m == int(g.degrees.sum())


def test_digraph_apply_net_dm_sign():
    c = from_coo([0, 0, 1], [1, 2, 2], n=3)
    g = DiGraph.from_csr(c)
    plan = updates.plan_update(
        inserts=edgebatch.from_arrays([2], [0]),
        deletes=edgebatch.from_arrays([0, 0], [1, 2]),
    )
    g, dm = g.apply(plan)
    assert dm == -1  # +1 insert, -2 deletes
    assert g.m == 2


def test_coo_galloping_merge_mixed_oracle():
    """The sort-free SortedCOO rebuild (DESIGN.md §12): deletes, weight
    upserts and interleaved new keys land exactly where the old
    full-re-sort put them, across several churn rounds."""
    rng = np.random.default_rng(77)
    n = 40
    src, dst = synthetic.uniform_edges(rng, n, 220)
    c = from_coo(src, dst, n=n)
    g = REPRESENTATIONS["coo"].from_csr(c)
    sets = [set(x) for x in c.to_edge_sets()]
    for _ in range(4):
        ins = edgebatch.random_insertions(rng, n, 25)
        dele = edgebatch.random_deletions(rng, g.to_csr(), 25)
        plan = updates.plan_update(inserts=ins, deletes=dele)
        g, _ = g.apply(plan)
        sets = _apply_oracle(sets, plan)
        got = g.to_edge_sets()
        while len(got) < len(sets):
            got.append(set())
        assert got[: len(sets)] == sets
        # the rebuilt buffer stays (src, dst)-lexsorted with SENTINEL tail
        s = np.asarray(g.src)
        d = np.asarray(g.dst)
        keys = (s[: g.m].astype(np.int64) << 32) | d[: g.m].astype(np.int64)
        assert (np.diff(keys) > 0).all()
        assert (s[g.m :] == SENT).all()


def test_coo_merge_weight_upsert_in_place():
    """Re-inserting an existing edge replaces its weight, no duplicate."""
    g = REPRESENTATIONS["coo"].from_csr(
        from_coo([0, 0, 1], [1, 2, 0], [1.0, 2.0, 3.0], n=3)
    )
    g, dm = g.apply(
        updates.plan_update(
            inserts=edgebatch.from_arrays([0], [2], [9.5])
        )
    )
    assert dm == 0 and g.m == 3
    s, d, w = np.asarray(g.src), np.asarray(g.dst), np.asarray(g.wgt)
    i = int(np.nonzero((s == 0) & (d == 2))[0][0])
    assert w[i] == np.float32(9.5)
