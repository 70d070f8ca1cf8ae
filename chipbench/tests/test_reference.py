"""The reference against the served store, and against hand counts."""
import numpy as np
import pytest

from chipbench import graph, reference
from chipbench.generators import kronecker

KRON = {"graph": {"scale": 8, "edge_factor": 8, "A": 0.57, "B": 0.19, "C": 0.19}}


def _small_graph(seed=0, n=60, p=0.06):
    rng = np.random.default_rng(seed)
    a = rng.random((n, n)) < p
    a = a | a.T
    np.fill_diagonal(a, False)
    src, dst = np.nonzero(a)
    off = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=off[1:])
    csr = graph.HostCSR(off, dst.astype(np.int32),
                        np.full(dst.shape[0], 0.5, np.float32), n)
    return a, csr


def _hand_walk(adj, seeds, steps):
    """Loops over the dense adjacency: the walk and its byte count."""
    n = adj.shape[0]
    x = np.zeros(n)
    for s in seeds:
        x[s] += 1
    nbytes = 0
    for _ in range(steps):
        y = np.zeros(n)
        for u in range(n):
            for v in range(n):
                if adj[u, v]:
                    y[u] += x[v]
                    if x[v] != 0:
                        nbytes += 8
        nbytes += 8 * int(np.count_nonzero(y))
        x = y
    return x, nbytes


@pytest.mark.parametrize("share", [1.0, 0.0])
def test_walk_and_bytes_match_a_hand_count(share, monkeypatch):
    """Sparse (share 1) and dense (share 0) steps, after a batch that
    adds, removes and re-weights edges."""
    adj, csr = _small_graph()
    st = reference.EdgeState(csr.offsets, csr.dst, csr.wgt, csr.n)
    rng = np.random.default_rng(1)
    ins = rng.integers(0, csr.n, (25, 2))
    src, dst = np.nonzero(adj)
    dels = rng.choice(src.shape[0], 20, replace=False)
    st.apply(ins[:, 0], ins[:, 1], np.ones(25, np.float32),
             src[dels], dst[dels])
    adj = adj.copy()
    adj[src[dels], dst[dels]] = False
    adj[ins[:, 0], ins[:, 1]] = True
    monkeypatch.setattr(reference, "SPARSE_SHARE", share)
    seeds = [rng.integers(0, csr.n, 4) for _ in range(3)]
    vis, nbytes = reference.Walker(st).walk(st.delta(), seeds, 3)
    for j, s in enumerate(seeds):
        want, want_bytes = _hand_walk(adj, s, 3)
        np.testing.assert_array_equal(vis.column(j), want)
        assert nbytes[j] == want_bytes
    keys, _w = st.edges()
    u, v = np.nonzero(adj)
    np.testing.assert_array_equal(keys, np.sort(reference.keys_of(u, v)))


def test_reference_agrees_with_served_walks_across_generations():
    """Walks served between update batches, each at the generation its
    answer names, and the Pallas walk (interpret mode) of every sealed
    generation, agree with the reference."""
    from repro.core import DiGraph, edgebatch, updates
    from repro.core import csr as csr_mod
    from repro.runtime import serve

    base, extras = kronecker.generate(KRON, 4)
    rep = DiGraph.from_csr(csr_mod.CSR(base.offsets, base.dst, base.wgt,
                                       base.n, base.m))
    srv = serve.WalkServer(rep, batch_max=4).start()
    rng = np.random.default_rng(2)
    walks, acked, pallas = [], [], []
    try:
        for _ in range(3):
            ts = [(s, srv.submit_walk(s, steps=4))
                  for s in (rng.integers(0, base.n, 4) for _ in range(4))]
            for s, t in ts:
                walks.append((s, t.result(60), t.generation))
            gen = srv.generation
            s = rng.integers(0, base.n, 4)
            x0 = np.zeros((4, gen.image.nv), np.float32)
            np.add.at(x0[0], s, 1.0)
            out = gen.image.walk(4, visits0=x0, backend="pallas", interpret=True)
            pallas.append((s, np.asarray(out)[0], gen.gen_id))
            isrc, idst = kronecker.sample_edges(KRON, extras, rng, 40)
            ins = (isrc, idst, np.full(40, 0.25, np.float32))
            pick = rng.choice(base.m, 40, replace=False)
            dels = (base.src()[pick], base.dst[pick])
            t = srv.submit_update(updates.plan_update(
                inserts=edgebatch.from_arrays(*ins),
                deletes=edgebatch.from_arrays(*dels)))
            t.result(60)
            acked.append((t.generation, ins, dels))
    finally:
        srv.stop()
    st = reference.EdgeState(base.offsets, base.dst, base.wgt, base.n)
    walker = reference.Walker(st)
    pending = list(acked)
    worst = 0.0
    for seeds, got, g in sorted(walks + pallas, key=lambda w: w[2]):
        while pending and pending[0][0] <= g:
            _g, ins, dels = pending.pop(0)
            st.apply(*ins, *dels)
        vis, _ = walker.walk(st.delta(), [seeds], 4)
        worst = max(worst, reference.walk_error(got, vis.column(0)))
    assert len({g for _s, _v, g in walks}) == 3
    assert worst < 1e-5


def test_read_wal_decodes_what_the_journal_wrote(tmp_path):
    from repro.core import edgebatch, updates
    from repro.runtime import durable

    rng = np.random.default_rng(3)
    j = durable.UpdateJournal(str(tmp_path), segment_bytes=600)
    batches = []
    for _ in range(5):
        ins = (rng.integers(0, 50, 9), rng.integers(0, 50, 9),
               rng.uniform(0.1, 1, 9).astype(np.float32))
        dels = (rng.integers(0, 50, 7), rng.integers(0, 50, 7))
        j.append(updates.plan_update(
            inserts=edgebatch.from_arrays(*ins),
            deletes=edgebatch.from_arrays(*dels)), 50)
        batches.append((ins, dels))
    j.close()
    records, bad = reference.read_wal(str(tmp_path))
    assert bad == 0 and len(records) == 5
    assert len(j.segments()) > 1
    for (ins, dels), (_seq, src, dst, w, d) in zip(batches, records):
        keys, wgt, dele = reference.canonical_ops(*ins, *dels)
        np.testing.assert_array_equal(reference.keys_of(src, dst), keys)
        np.testing.assert_array_equal(w, wgt)
        np.testing.assert_array_equal(d, dele)
