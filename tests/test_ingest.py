"""Ingest-engine tests (DESIGN.md §10): tokenizer, fixed-width fast
path, csr_build engines, arena parity, chunked mmap loads, and the
per-buffer copy-on-write clone/snapshot protocol."""
import os

import numpy as np
import pytest

from repro.core import DiGraph, REPRESENTATIONS, csr as csr_mod, edgebatch
from repro.io import mtx, synthetic
from repro.kernels.csr_build import kernel as cb_kernel, ops as cb_ops, ref as cb_ref


def _write(tmp_path, body: str) -> str:
    p = str(tmp_path / "g.mtx")
    with open(p, "w") as f:
        f.write(body)
    return p


def _eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# tokenizer / parser
# ---------------------------------------------------------------------------
def test_general_tokenizer_matches_fixed_path(tmp_path):
    c = synthetic.make_graph("social", scale=9, edge_factor=4, seed=3)
    p = str(tmp_path / "g.mtx")
    mtx.write_mtx(p, c)
    a = mtx.load_mtx(p)                 # fixed-width fast path
    b = mtx.load_mtx(p, fixed=False)    # general mask/cumsum tokenizer
    _eq(a.offsets, b.offsets)
    _eq(a.dst, b.dst)
    _eq(a.wgt, b.wgt)
    _eq(a.dst, c.dst)


def test_ragged_whitespace_and_signs(tmp_path):
    body = (
        "%%MatrixMarket matrix coordinate real general\n"
        "% comment\n%% another\n% third\n"
        "4 4 5\n"
        "1 2 1.5\n"
        "  2\t3   -2.25\n"
        "3   4 +3e2\n"
        "4 1 .5\n"
        "1 1 5.\n"
    )
    c = mtx.load_mtx(_write(tmp_path, body))
    got = dict()
    o = np.asarray(c.offsets)
    d = np.asarray(c.dst)
    w = np.asarray(c.wgt)
    for u in range(4):
        for j in range(o[u], o[u + 1]):
            got[(u, int(d[j]))] = float(w[j])
    assert got == {
        (0, 1): 1.5, (1, 2): -2.25, (2, 3): 300.0, (3, 0): 0.5, (0, 0): 5.0
    }


def test_scientific_weights_roundtrip(tmp_path):
    vals = np.array(
        [1.5e-2, -2.25e1, 3.25e-30, -4.5e30, 0.0, 1.0, -1.0],
        np.float32,
    )
    n = vals.shape[0]
    src = np.arange(n)
    dst = (src + 1) % n
    c = csr_mod.from_coo(src, dst, vals, n=n)
    p = str(tmp_path / "e.mtx")
    mtx.write_mtx(p, c)
    for fixed in (True, False):
        c2 = mtx.load_mtx(p, fixed=fixed)
        _eq(c2.wgt, c.wgt)


def test_pattern_symmetric(tmp_path):
    body = (
        "%%MatrixMarket matrix coordinate pattern symmetric\n"
        "% a comment line\n4 4 3\n2 1\n3 1\n4 3\n"
    )
    c = mtx.load_mtx(_write(tmp_path, body))
    assert c.n == 4 and c.m == 6
    assert c.to_edge_sets() == [{1, 2}, {0}, {0, 3}, {2}]


def test_truncated_body_raises(tmp_path):
    body = (
        "%%MatrixMarket matrix coordinate real general\n"
        "4 4 5\n1 2 1.0\n2 3 1.0\n"
    )
    with pytest.raises(ValueError, match="truncated|tokens"):
        mtx.load_mtx(_write(tmp_path, body))


def test_malformed_token_count_raises(tmp_path):
    body = (
        "%%MatrixMarket matrix coordinate real general\n"
        "4 4 2\n1 2 1.0\n2 3 1.0 7 8\n"
    )
    with pytest.raises(ValueError):
        mtx.load_mtx(_write(tmp_path, body), fixed=False)


def test_garbage_byte_raises(tmp_path):
    body = (
        "%%MatrixMarket matrix coordinate real general\n"
        "4 4 2\n1 2 1.0\nx y 1.0\n"
    )
    with pytest.raises(ValueError):
        mtx.load_mtx(_write(tmp_path, body), fixed=False)


def test_out_of_range_coordinate_raises(tmp_path):
    body = (
        "%%MatrixMarket matrix coordinate real general\n"
        "4 4 2\n1 2 1.0\n9 1 1.0\n"
    )
    with pytest.raises(ValueError, match="out of range"):
        mtx.load_mtx(_write(tmp_path, body))


def test_partition_parallel_parse_invariance(tmp_path):
    c = synthetic.make_graph("uniform", scale=10, edge_factor=8, seed=5)
    p = str(tmp_path / "u.mtx")
    mtx.write_mtx(p, c)
    base = mtx.load_mtx(p, num_partitions=1)
    # force the thread fan-out regardless of body size
    old = mtx._PARALLEL_MIN_BYTES
    mtx._PARALLEL_MIN_BYTES = 1
    try:
        for rho in (2, 3):
            for fixed in (True, False):
                c2 = mtx.load_mtx(p, num_partitions=rho, fixed=fixed)
                _eq(c2.offsets, base.offsets)
                _eq(c2.dst, base.dst)
                _eq(c2.wgt, base.wgt)
    finally:
        mtx._PARALLEL_MIN_BYTES = old


def test_compiled_parser_matches_numpy_folds(tmp_path):
    """io/_cparse.py (when buildable) must be bit-identical to the sgemm
    fold path, including negative weights and id range validation."""
    rng = np.random.default_rng(41)
    src, dst = synthetic.uniform_edges(rng, 200, 900)
    w = (rng.uniform(0.5, 1.5, 900) * np.where(rng.random(900) < 0.3, -1, 1))
    c = csr_mod.from_coo(src, dst, w.astype(np.float32), n=200)
    p = str(tmp_path / "c.mtx")
    mtx.write_mtx(p, c)
    a = mtx.load_mtx(p)
    old = mtx.USE_C_PARSE
    try:
        mtx.USE_C_PARSE = False
        b = mtx.load_mtx(p)
    finally:
        mtx.USE_C_PARSE = old
    _eq(a.offsets, b.offsets)
    _eq(a.dst, b.dst)
    _eq(a.wgt, b.wgt)


def test_mmap_chunked_load_matches_whole_buffer(tmp_path):
    c = synthetic.make_graph("web", scale=9, edge_factor=4, seed=7)
    p = str(tmp_path / "m.mtx")
    mtx.write_mtx(p, c)
    whole = mtx.load_mtx(p)
    chunked = mtx.load_mtx(p, mmap_threshold=0, chunk_bytes=1 << 12)
    _eq(chunked.offsets, whole.offsets)
    _eq(chunked.dst, whole.dst)
    _eq(chunked.wgt, whole.wgt)


def test_write_mtx_is_valid_for_foreign_parsers(tmp_path):
    """The fixed-width writer must stay plain Matrix Market (python parse)."""
    c = synthetic.make_graph("road", scale=8, seed=2)
    p = str(tmp_path / "r.mtx")
    mtx.write_mtx(p, c)
    src, dst, wgt = [], [], []
    with open(p) as f:
        assert f.readline().startswith("%%MatrixMarket")
        n, n2, m = map(int, f.readline().split())
        for line in f:
            a, b, w = line.split()
            src.append(int(a) - 1)
            dst.append(int(b) - 1)
            wgt.append(float(w))
    assert len(src) == c.m
    c2 = csr_mod.from_coo(src, dst, np.array(wgt, np.float32), n=n, dedup=False)
    _eq(c2.dst, c.dst)
    np.testing.assert_allclose(
        np.asarray(c2.wgt), np.asarray(c.wgt), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# csr_build engines
# ---------------------------------------------------------------------------
def _random_coo(seed, n=64, m=400):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, n, m),
        rng.integers(0, n, m),
        rng.uniform(0.5, 1.5, m).astype(np.float32),
        n,
    )


@pytest.mark.parametrize("seed", [0, 1])
def test_count_degrees_engines_agree(seed):
    src, _, _, n = _random_coo(seed)
    ref = cb_ref.count_degrees_reference(src, n)
    host = cb_ops.count_degrees(src, n, engine="host")
    xla = np.asarray(cb_ops.count_degrees(src, n, engine="xla"))
    pallas = np.asarray(
        cb_ops.count_degrees(src, n, engine="pallas", interpret=True)
    )
    _eq(host, ref)
    _eq(xla, ref)
    _eq(pallas, ref)


def test_pallas_degree_kernel_tiles():
    src = np.arange(300, dtype=np.int64) % 130
    tiles = np.full(384, 256, np.int32)
    tiles[:300] = src
    deg = np.asarray(
        cb_kernel.count_degrees_pallas(
            np.asarray(tiles.reshape(-1, cb_kernel.EB)), nv=256, interpret=True
        )
    )
    _eq(deg[:130], cb_ref.count_degrees_reference(src, 130))


@pytest.mark.parametrize("seed", [3, 4])
def test_from_coo_engine_parity(seed):
    src, dst, wgt, n = _random_coo(seed)
    host = csr_mod.from_coo(src, dst, wgt, n=n, dedup=False, engine="host")
    xla = csr_mod.from_coo(src, dst, wgt, n=n, dedup=False, engine="xla")
    o_ref, d_ref, w_ref = cb_ref.coo_to_csr_reference(src, dst, wgt, n=n)
    _eq(host.offsets, o_ref)
    _eq(host.dst, d_ref)
    _eq(xla.offsets, o_ref)
    _eq(xla.dst, xla.dst)
    _eq(np.asarray(xla.dst), d_ref)
    # weights: dedup=False keeps duplicates; ref emits them in file order
    np.testing.assert_allclose(np.asarray(host.wgt), w_ref, rtol=0)


def test_from_coo_presorted_shortcut_matches_sorted():
    src, dst, wgt, n = _random_coo(9)
    a = csr_mod.from_coo(src, dst, wgt, n=n, dedup=False)
    # feed the already-sorted edges back through (triggers the skip path)
    b = csr_mod.from_coo(
        np.repeat(np.arange(n), np.diff(np.asarray(a.offsets))),
        np.asarray(a.dst),
        np.asarray(a.wgt),
        n=n,
        dedup=False,
    )
    _eq(a.offsets, b.offsets)
    _eq(a.dst, b.dst)
    _eq(a.wgt, b.wgt)


def test_arena_image_engines_and_reference():
    from repro.core import alloc

    src, dst, wgt, n = _random_coo(11)
    c = csr_mod.from_coo(src, dst, wgt, n=n, dedup=True)
    degrees = np.diff(np.asarray(c.offsets))
    caps = np.where(degrees > 0, alloc.edge_capacities(degrees), 0)
    csum = np.cumsum(caps)
    starts = np.where(caps > 0, csum - caps, -1)
    total = int(csum[-1])
    cap_e = alloc.next_pow2(max(total, 2))
    cap_v = n + 7
    args = (c.offsets, c.dst, c.wgt, starts, caps, cap_e, cap_v)
    r_d, r_w, r_r = cb_ref.arena_image_reference(*args)
    got = cb_ops.arena_image(*args)
    _eq(got[0], r_d)
    _eq(got[1], r_w)
    _eq(got[2], r_r)


def test_load_digraph_bit_identical_to_host_from_csr(tmp_path):
    c = synthetic.make_graph("web", scale=9, edge_factor=4, seed=13)
    p = str(tmp_path / "w.mtx")
    mtx.write_mtx(p, c)
    g1 = mtx.load_digraph(p)
    g2 = DiGraph.from_csr(mtx.load_mtx(p))
    _eq(g1.dst, g2.dst)
    _eq(g1.wgt, g2.wgt)
    _eq(g1.slot_rows, g2.slot_rows)
    assert (g1.n, g1.m) == (g2.n, g2.m)
    np.testing.assert_array_equal(g1.starts, g2.starts)
    np.testing.assert_array_equal(g1.capacities, g2.capacities)


# ---------------------------------------------------------------------------
# clone isolation + per-buffer COW (dense-oracle checks)
# ---------------------------------------------------------------------------
def _dense(g, n):
    c = g.to_csr()
    a = np.zeros((n, n), np.float32)
    d = c.to_dense()
    a[: d.shape[0], : d.shape[1]] = d
    return a


@pytest.mark.parametrize("name,cls", list(REPRESENTATIONS.items()))
def test_clone_isolation_dense_oracle(name, cls):
    rng = np.random.default_rng(21)
    src, dst = synthetic.uniform_edges(rng, 48, 300)
    c = csr_mod.from_coo(src, dst, n=48)
    g = cls.from_csr(c)
    before = _dense(g, 64)
    cl = g.clone()
    # mutate the clone: the original must not move (and vice versa)
    cl, _ = cl.add_edges(edgebatch.random_insertions(rng, 60, 25))
    cl, _ = cl.remove_edges(edgebatch.random_deletions(rng, cl.to_csr(), 10))
    np.testing.assert_array_equal(_dense(g, 64), before)
    after_clone = _dense(cl, 64)
    g, _ = g.add_edges(edgebatch.random_insertions(rng, 60, 25))
    np.testing.assert_array_equal(_dense(cl, 64), after_clone)


@pytest.mark.parametrize("name,cls", list(REPRESENTATIONS.items()))
def test_post_snapshot_mutation_isolation(name, cls):
    rng = np.random.default_rng(23)
    src, dst = synthetic.uniform_edges(rng, 48, 300)
    c = csr_mod.from_coo(src, dst, n=48)
    g = cls.from_csr(c)
    snap = g.snapshot()
    frozen = _dense(snap, 64)
    for _ in range(3):
        g, _ = g.add_edges(edgebatch.random_insertions(rng, 60, 20))
        g, _ = g.remove_edges(edgebatch.random_deletions(rng, g.to_csr(), 8))
        np.testing.assert_array_equal(_dense(snap, 64), frozen)


def test_digraph_cow_detaches_only_touched_buffers():
    """A non-growing post-snapshot update must keep sharing slot_rows."""
    rng = np.random.default_rng(29)
    src, dst = synthetic.uniform_edges(rng, 32, 400)
    g = DiGraph.from_csr(csr_mod.from_coo(src, dst, n=32))
    snap = g.snapshot()
    assert g.sealed and snap.sealed
    # delete a handful of edges: no CP2AA class changes, no block moves
    b = edgebatch.random_deletions(rng, g.to_csr(), 4)
    g, _ = g.remove_edges(b)
    assert g.slot_rows is snap.slot_rows, "owner map should stay shared"
    assert g.dst is not snap.dst and g.wgt is not snap.wgt
    assert "slot_rows" in g._sealed and "dst" not in g._sealed
    # a growing update (class spill) must now detach the owner map too
    hub = np.zeros(600, np.int64)
    g, _ = g.add_edges(edgebatch.from_arrays(hub, 40 + np.arange(600)))
    assert g.slot_rows is not snap.slot_rows


def test_lazy_cow_base_arrays_never_copied():
    rng = np.random.default_rng(31)
    src, dst = synthetic.uniform_edges(rng, 32, 300)
    from repro.core import LazyCSR

    g = LazyCSR.from_csr(csr_mod.from_coo(src, dst, n=32))
    snap = g.snapshot()
    g, _ = g.remove_edges(edgebatch.random_deletions(rng, g.to_csr(), 5))
    # zombie marking detaches only the masks
    assert g.base_dst is snap.base_dst and g.base_wgt is snap.base_wgt
    assert g.dead is not snap.dead
    g, _ = g.add_edges(edgebatch.random_insertions(rng, 32, 5))
    assert g.base_dst is snap.base_dst, "appends must not copy the base"


def test_digraph_clone_single_fused_dispatch(monkeypatch):
    """clone() must route every device buffer through ONE fused_copy call."""
    from repro.core import util as core_util

    rng = np.random.default_rng(37)
    src, dst = synthetic.uniform_edges(rng, 32, 200)
    g = DiGraph.from_csr(csr_mod.from_coo(src, dst, n=32))
    calls = []
    real = core_util.fused_copy

    def spy(*arrays):
        calls.append(len(arrays))
        return real(*arrays)

    monkeypatch.setattr(core_util, "fused_copy", spy)
    monkeypatch.setattr(
        "repro.core.digraph.util.fused_copy", spy, raising=False
    )
    cl = g.clone()
    assert calls == [3], f"expected one fused 3-buffer copy, got {calls}"
    _eq(cl.dst, g.dst)
    assert cl.dst is not g.dst
