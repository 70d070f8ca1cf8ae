"""The device graph generators: deterministic per seed, the
specification's edge counts, permuted Kronecker labels."""
import numpy as np
import pytest

from chipbench import graph
from chipbench.generators import kronecker, road

KRON = {"graph": {"scale": 9, "edge_factor": 16, "A": 0.57, "B": 0.19, "C": 0.19}}
ROAD = {"graph": {"vertices": 4000, "shortcuts": 250}}


def _keys(csr):
    return (csr.src().astype(np.int64) << 32) | csr.dst.astype(np.int64)


def test_kronecker_deterministic_per_seed():
    a, ea = kronecker.generate(KRON, 2**31 + 17)
    b, eb = kronecker.generate(KRON, 2**31 + 17)
    c, _ = kronecker.generate(KRON, 2**31 + 18)
    assert np.array_equal(a.offsets, b.offsets)
    assert np.array_equal(a.dst, b.dst) and np.array_equal(a.wgt, b.wgt)
    assert np.array_equal(ea["perm"], eb["perm"])
    assert not np.array_equal(_keys(a), _keys(c))


def test_large_seeds_keep_their_high_bits():
    import jax

    k1 = jax.random.key_data(graph.device_key(2**40 + 3))
    k2 = jax.random.key_data(graph.device_key(3))
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))


def test_kronecker_edge_count_is_the_specifications():
    """M = edgefactor * 2^SCALE generated edges; the graph is their
    symmetric closure without self-loops or duplicates."""
    key = graph.device_key(5, 1)
    g = KRON["graph"]
    src, dst = kronecker.edges(
        key, scale=g["scale"], edge_factor=g["edge_factor"],
        abc=(g["A"], g["B"], g["C"]),
    )
    assert src.shape[0] == g["edge_factor"] << g["scale"]
    csr, extras = kronecker.generate(KRON, 5)
    perm = extras["perm"].astype(np.int64)
    src, dst = perm[np.asarray(src)], perm[np.asarray(dst)]
    keep = src != dst
    u = np.concatenate([src[keep], dst[keep]])
    v = np.concatenate([dst[keep], src[keep]])
    want = np.unique((u << 32) | v)
    assert np.array_equal(_keys(csr), want)
    assert csr.m == want.shape[0]


def test_kronecker_labels_are_permuted():
    """Unpermuted, vertex 0 is the hub; the permutation moves it."""
    csr, extras = kronecker.generate(KRON, 11)
    perm = extras["perm"]
    n = 1 << KRON["graph"]["scale"]
    assert np.array_equal(np.sort(perm), np.arange(n))
    deg = np.diff(csr.offsets)
    assert deg[perm[0]] == deg.max()
    assert perm[0] != 0 and deg[0] < deg.max()


def test_kronecker_graph_is_symmetric_with_pair_weights():
    csr, _ = kronecker.generate(KRON, 3)
    k = _keys(csr)
    assert np.all(k[1:] > k[:-1])
    assert np.all(csr.src() != csr.dst)
    rev = (csr.dst.astype(np.int64) << 32) | csr.src().astype(np.int64)
    order = np.argsort(rev)
    assert np.array_equal(rev[order], k)
    assert np.array_equal(csr.wgt[order], csr.wgt)
    assert np.all((csr.wgt > 0) & (csr.wgt <= 1))


def test_road_edge_count_is_exact():
    csr, _ = road.generate(ROAD, 9)
    g = ROAD["graph"]
    assert csr.m == 2 * (g["vertices"] - 1 + g["shortcuts"])
    assert np.all(np.diff(_keys(csr)) > 0)
    assert np.diff(csr.offsets).max() <= 2 + 2 * 8


@pytest.mark.parametrize("gen,cfg", [(kronecker, KRON), (road, ROAD)])
def test_sampled_edges_stay_in_range(gen, cfg):
    csr, extras = gen.generate(cfg, 1)
    src, dst = gen.sample_edges(cfg, extras, np.random.default_rng(0), 500)
    assert src.shape == dst.shape == (500,)
    assert src.min() >= 0 and dst.min() >= 0
    assert max(src.max(), dst.max()) < csr.n


def test_every_seed_offers_the_same_arrivals_from_the_window_start():
    from chipbench.traffic import arrival_offsets

    a = arrival_offsets(2.0, 51.0, 7)
    b = arrival_offsets(2.0, 51.0, 2**31 + 7)
    assert a.shape == b.shape == (102,)
    assert a[0] == b[0] == 0.0 and a[-1] < 51.0
    assert np.all(np.diff(a) > 0)
    # the gaps are one set in two orders; the last gap of each falls
    # after the window
    da, db = np.round(np.diff(a), 9), np.round(np.diff(b), 9)
    assert np.intersect1d(da, db).shape[0] >= da.shape[0] - 1
