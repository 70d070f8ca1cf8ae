"""Multi-tenant walk serving (DESIGN.md §16): snapshot-isolated
generations, admission control / backpressure / deadline shedding,
graceful degradation through the breaker chain, the half-open breaker
protocol, and the fault-injected zero-lost contract."""
import threading
import time

import numpy as np
import pytest

from repro.core import REPRESENTATIONS, csr as csr_mod, edgebatch, updates, walk_image
from repro.kernels import fallback
from repro.launch import serve as launch_serve
from repro.runtime import faultinject
from repro.runtime import serve as serve_mod

N_V = 48


@pytest.fixture(autouse=True)
def _clean_fault_state():
    faultinject.disarm()
    fallback.BREAKER.reset()
    fallback.LAST_USED.clear()
    yield
    faultinject.disarm()
    fallback.BREAKER.reset()
    fallback.LAST_USED.clear()


@pytest.fixture(scope="module")
def base_csr():
    rng = np.random.default_rng(23)
    m = 220
    return csr_mod.from_coo(
        rng.integers(0, N_V, m),
        rng.integers(0, N_V, m),
        rng.random(m).astype(np.float32),
        n=N_V,
    )


def make_plan(rng, n=N_V, n_ins=12, n_del=6):
    ib = edgebatch.from_arrays(
        rng.integers(0, n, n_ins),
        rng.integers(0, n, n_ins),
        rng.random(n_ins).astype(np.float32),
    )
    db = edgebatch.from_arrays(
        rng.integers(0, n, n_del), rng.integers(0, n, n_del)
    )
    return updates.plan_update(inserts=ib, deletes=db)


def serve_and_verify(rep_kind, base, *, requests=24, update_every=4,
                     seed=3, **server_kw):
    """Run mixed traffic against ``rep_kind`` and return (stats, torn,
    checked) with the zero-lost ledger already asserted."""
    rep = REPRESENTATIONS[rep_kind].from_csr(base)
    srv = serve_mod.WalkServer(rep, **server_kw).start()
    rng = np.random.default_rng(seed)
    walks, upds = [], []
    for i in range(requests):
        if update_every and i % update_every == 0:
            plan = make_plan(rng)
            upds.append((srv.submit_update(plan), plan))
        walks.append(srv.submit_walk(rng.integers(0, N_V, 3), steps=3))
    for t in walks:
        assert t.wait(60.0)
    stats = srv.stop()
    srv.assert_no_lost()
    torn, checked = launch_serve.count_torn_reads(
        launch_serve.GenerationOracle(base), walks, upds
    )
    return stats, torn, checked


def _set_oracle_walk(edges: set, nv: int, row, steps: int, drop=None):
    """The set-of-tuples + ``np.add.at`` walk the array oracle replaced."""
    arr = np.array(sorted(edges), np.int64).reshape(-1, 2)
    s, d = arr[:, 0], arr[:, 1]
    v = np.asarray(row, np.float64)
    for _ in range(steps):
        nxt = np.zeros(nv, np.float64)
        np.add.at(nxt, s, v[d])
        if drop is not None:
            nxt[drop] = 0.0
        v = nxt
    return v


def test_array_oracle_matches_set_oracle_under_churn(base_csr):
    """The sorted-key GenerationOracle walks every generation of a churned
    stream exactly like the edge-set replica it replaced (re-inserts,
    deletes of present and absent edges, upserts, degraded rows)."""
    off = np.asarray(base_csr.offsets, np.int64)
    rows = np.repeat(np.arange(N_V), np.diff(off))
    edges = set(zip(rows.tolist(), np.asarray(base_csr.dst).tolist()))
    oracle = launch_serve.GenerationOracle(base_csr)
    rng = np.random.default_rng(5)
    for gen in range(1, 7):
        plans = [make_plan(rng, n_ins=20, n_del=15) for _ in range(2)]
        if gen == 3:  # delete edges that exist, then put some back
            live = sorted(edges)[:10]
            plans.append(updates.plan_update(deletes=edgebatch.from_arrays(
                [e[0] for e in live], [e[1] for e in live])))
            plans.append(updates.plan_update(inserts=edgebatch.from_arrays(
                [e[0] for e in live[:4]], [e[1] for e in live[:4]])))
        for plan in plans:
            oracle.record(gen, plan)
            for u, v, rm in zip(plan.q_src.tolist(), plan.q_dst.tolist(),
                                plan.q_del.tolist()):
                (edges.discard if rm else edges.add)((u, v))
        row = launch_serve.seed_visits_row(N_V, rng.integers(0, N_V, 3))
        drop = np.arange(8, 16) if gen % 2 else None
        np.testing.assert_array_equal(
            oracle.walk(gen, row, 3, drop_rows=drop),
            _set_oracle_walk(edges, N_V, row, 3, drop),
        )


def test_oracle_walk_many_matches_single_walks(base_csr):
    """k rows walked together equal k single walks, on a graph with
    empty rows and under a masked row set; an empty graph walks to 0."""
    oracle = launch_serve.GenerationOracle(base_csr)
    rng = np.random.default_rng(9)
    rows = np.stack([
        launch_serve.seed_visits_row(N_V, rng.integers(0, N_V, 4))
        for _ in range(5)
    ])
    for drop in (None, np.arange(0, N_V, 3)):
        many = oracle.walk_many(0, rows, 4, drop_rows=drop)
        assert many.shape == rows.shape
        for row, got in zip(rows, many):
            np.testing.assert_array_equal(
                got, oracle.walk(0, row, 4, drop_rows=drop)
            )
    empty = launch_serve.GenerationOracle(
        csr_mod.from_coo(np.zeros(0, np.int64), np.zeros(0, np.int64), n=N_V)
    )
    assert not empty.walk_many(0, rows, 2).any()


# ---------------------------------------------------------------------------
# snapshot isolation: every served walk is consistent with its generation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rep_kind", ["digraph", "chunked"])
def test_served_walks_match_generation_oracle(rep_kind, base_csr):
    stats, torn, checked = serve_and_verify(rep_kind, base_csr)
    assert stats["served"] == 24
    assert checked == 24 and torn == 0
    assert stats["seals"] >= 2  # updates actually advanced generations
    # seeded walks on a small graph: every step ran in the sparse form
    assert stats["walk_steps_sparse"] == 3 * stats["batches"] > 0
    assert stats["walk_steps_dense"] == 0


@pytest.mark.parametrize("rep_kind", ["coo", "lazy", "vector2d"])
def test_served_walks_match_oracle_all_reps(rep_kind, base_csr):
    stats, torn, checked = serve_and_verify(
        rep_kind, base_csr, requests=12, update_every=3
    )
    assert torn == 0 and checked == stats["served"] == 12
    assert stats["walk_steps_sparse"] > 0 and stats["walk_steps_dense"] == 0


def test_sealed_generation_immutable_under_writer(base_csr):
    """The COW contract directly: a sealed generation's walk result must
    not change while the live rep keeps applying plans."""
    for kind in ("digraph", "chunked"):
        rep = REPRESENTATIONS[kind].from_csr(base_csr)
        gen = walk_image.seal_generation(rep, 1)
        before = np.asarray(gen.walk(3)).copy()
        rng = np.random.default_rng(5)
        for _ in range(4):
            rep, _ = rep.apply(make_plan(rng))
            rep.reverse_walk(2)  # force flush/patch of the live image
        np.testing.assert_array_equal(np.asarray(gen.walk(3)), before)


def test_seal_api_guards(base_csr):
    rep = REPRESENTATIONS["chunked"].from_csr(base_csr)
    img = rep.to_walk_image()
    gen = img.seal(7)
    assert gen.generation == 7 and gen._frozen
    with pytest.raises(RuntimeError, match="read-only"):
        gen.queue(make_plan(np.random.default_rng(0)))
    img.queue(make_plan(np.random.default_rng(1)))
    with pytest.raises(ValueError, match="unflushed"):
        img.seal(8)
    shared = REPRESENTATIONS["digraph"].from_csr(base_csr).to_walk_image()
    with pytest.raises(ValueError, match="shared"):
        shared.seal(9)


def test_concurrent_reader_writer_sweep(base_csr):
    """Deterministic concurrent sweep (always runs): a writer thread
    applies+seals while reader threads walk; every served walk must
    match the oracle for its own sealed generation — no torn reads."""
    for kind in ("digraph", "chunked"):
        rep = REPRESENTATIONS[kind].from_csr(base_csr)
        srv = serve_mod.WalkServer(rep, batch_max=4).start()
        rng = np.random.default_rng(17)
        upds, walks, stop = [], [], threading.Event()
        lock = threading.Lock()

        def reader(seed):
            r = np.random.default_rng(seed)
            while not stop.is_set():
                t = srv.submit_walk(r.integers(0, N_V, 2), steps=2)
                t.wait(30.0)
                with lock:
                    walks.append(t)

        threads = [
            threading.Thread(target=reader, args=(s,)) for s in (31, 32, 33)
        ]
        for th in threads:
            th.start()
        for _ in range(8):
            plan = make_plan(rng)
            upds.append((srv.submit_update(plan), plan))
            time.sleep(0.01)
        for t, _ in upds:
            assert t.wait(30.0)
        stop.set()
        for th in threads:
            th.join(30.0)
        srv.stop()
        srv.assert_no_lost()
        torn, checked = launch_serve.count_torn_reads(
            launch_serve.GenerationOracle(base_csr), walks, upds
        )
        assert checked > 0 and torn == 0, kind


def test_hypothesis_reader_writer_sweep(base_csr):
    """Hypothesis-driven schedules over the same contract (gated: the
    container may not ship hypothesis)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=5, deadline=None)
    @hyp.given(
        seed=st.integers(0, 2**16),
        n_updates=st.integers(1, 6),
        batch_max=st.sampled_from([1, 2, 8]),
        rep_kind=st.sampled_from(["digraph", "chunked"]),
    )
    def inner(seed, n_updates, batch_max, rep_kind):
        rep = REPRESENTATIONS[rep_kind].from_csr(base_csr)
        srv = serve_mod.WalkServer(rep, batch_max=batch_max).start()
        rng = np.random.default_rng(seed)
        walks, upds = [], []
        for _ in range(n_updates):
            plan = make_plan(rng)
            upds.append((srv.submit_update(plan), plan))
            for _ in range(int(rng.integers(1, 4))):
                walks.append(
                    srv.submit_walk(rng.integers(0, N_V, 2), steps=2)
                )
        for t in walks:
            assert t.wait(60.0)
        srv.stop()
        srv.assert_no_lost()
        torn, checked = launch_serve.count_torn_reads(
            launch_serve.GenerationOracle(base_csr), walks, upds
        )
        assert torn == 0 and checked == len(walks)

    inner()


# ---------------------------------------------------------------------------
# admission control: backpressure, deadlines, shedding
# ---------------------------------------------------------------------------


def test_backpressure_rejects_with_retry_after(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep, max_queue=4, batch_max=2).start()
    tickets = [srv.submit_walk([1, 2], steps=2) for _ in range(120)]
    for t in tickets:
        assert t.wait(60.0)
    stats = srv.stop()
    srv.assert_no_lost()
    rejected = [t for t in tickets if t.status == serve_mod.REJECTED]
    assert stats["rejected_backpressure"] == len(rejected) > 0
    for t in rejected:
        assert t.reason == "backpressure"
        assert t.retry_after is not None and t.retry_after > 0
        with pytest.raises(serve_mod.RejectedError, match="backpressure"):
            t.result()


def test_expired_requests_are_shed_not_walked(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep, batch_max=1, max_queue=512).start()
    tickets = [
        srv.submit_walk([1], steps=2, timeout=1e-4) for _ in range(60)
    ]
    for t in tickets:
        assert t.wait(60.0)
    stats = srv.stop()
    srv.assert_no_lost()
    assert stats["shed_expired"] > 0
    shed = [t for t in tickets if t.reason == "expired"]
    assert len(shed) == stats["shed_expired"]


def test_bad_seeds_rejected_cleanly(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep).start()
    bad = srv.submit_walk([N_V + 100], steps=2)
    ok = srv.submit_walk([1], steps=2)
    assert bad.wait(30.0) and ok.wait(30.0)
    srv.stop()
    srv.assert_no_lost()
    assert bad.status == serve_mod.REJECTED
    assert bad.reason == "seed_out_of_range"
    assert ok.status == serve_mod.SERVED


def test_shutdown_rejects_new_requests(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep).start()
    srv.stop()
    t = srv.submit_walk([1], steps=2)
    assert t.status == serve_mod.REJECTED and t.reason == "shutdown"
    srv.assert_no_lost()


# ---------------------------------------------------------------------------
# fault-injected audits: enqueue / seal / dispatch boundaries
# ---------------------------------------------------------------------------


def test_enqueue_fault_is_clean_rejection(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep).start()
    faultinject.arm("serve.enqueue", times=1)
    t1 = srv.submit_walk([1], steps=2)
    t2 = srv.submit_walk([2], steps=2)
    assert t1.status == serve_mod.REJECTED and t1.reason == "enqueue_fault"
    assert t2.wait(30.0) and t2.status == serve_mod.SERVED
    faultinject.disarm()
    srv.stop()
    srv.assert_no_lost()


def test_dispatch_fault_retried_zero_lost(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep).start()
    faultinject.arm("serve.dispatch", times=1)
    tickets = [srv.submit_walk([1, 2], steps=2) for _ in range(8)]
    for t in tickets:
        assert t.wait(60.0)
    stats = srv.stop()
    faultinject.disarm()
    srv.assert_no_lost()
    assert stats["served"] == 8 and stats["dispatch_retries"] >= 1


def test_dispatch_fault_exhausted_fails_visibly(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep, dispatch_retries=1).start()
    faultinject.arm("serve.dispatch", times=50)
    t = srv.submit_walk([1], steps=2)
    assert t.wait(60.0)
    stats = srv.stop()
    faultinject.disarm()
    srv.assert_no_lost()
    assert t.status == serve_mod.FAILED and stats["failed"] == 1
    with pytest.raises(RuntimeError, match="request failed"):
        t.result()


def test_seal_fault_keeps_readers_on_previous_generation(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep).start()
    faultinject.arm("serve.seal", times=1)
    plan = make_plan(np.random.default_rng(9))
    ut = srv.submit_update(plan)
    assert ut.wait(30.0)  # writer retried the seal and acked
    wt = srv.submit_walk([1, 2], steps=2)
    assert wt.wait(30.0)
    stats = srv.stop()
    faultinject.disarm()
    srv.assert_no_lost()
    assert stats["seal_failures"] >= 1
    assert ut.status == serve_mod.SERVED and ut.generation == 1
    assert wt.generation >= 1
    torn, checked = launch_serve.count_torn_reads(
        launch_serve.GenerationOracle(base_csr), [wt], [(ut, plan)]
    )
    assert checked == 1 and torn == 0


def test_pallas_trip_mid_traffic_served_via_fallback(base_csr):
    """ISSUE acceptance: an injected pallas failure mid-traffic completes
    via the breaker chain with zero lost requests."""
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep, walk_backend="pallas", batch_max=4).start()
    faultinject.arm("slot_walk.pallas", times=2)
    tickets = [srv.submit_walk([1, 2], steps=2) for _ in range(12)]
    for t in tickets:
        assert t.wait(60.0)
    stats = srv.stop()
    faultinject.disarm()
    srv.assert_no_lost()
    assert stats["served"] == 12
    assert stats["breaker_fallbacks"] >= 1
    assert fallback.LAST_USED.get("slot_walk") in ("xla", "ref")


@pytest.mark.timeout(120)
def test_serve_soak_mixed_traffic(base_csr):
    """Soak: sustained mixed traffic with a mid-run injected dispatch
    fault; everything resolves, torn_reads == 0 (explicit per-test
    timeout so a queue bug can never hang tier-1)."""
    rep = REPRESENTATIONS["chunked"].from_csr(base_csr)
    srv = serve_mod.WalkServer(rep, batch_max=8, max_queue=64).start()
    rng = np.random.default_rng(41)
    walks, upds = [], []
    for i in range(120):
        if i % 6 == 0:
            plan = make_plan(rng)
            upds.append((srv.submit_update(plan), plan))
        if i == 60:
            faultinject.arm("serve.dispatch", times=2)
        walks.append(srv.submit_walk(rng.integers(0, N_V, 2), steps=2))
    for t in walks:
        assert t.wait(120.0)
    srv.stop()
    faultinject.disarm()
    stats = srv.assert_no_lost()
    torn, checked = launch_serve.count_torn_reads(
        launch_serve.GenerationOracle(base_csr), walks, upds
    )
    assert torn == 0 and checked == stats["served"] > 0


# ---------------------------------------------------------------------------
# half-open circuit breaker
# ---------------------------------------------------------------------------


def test_breaker_half_open_probe_then_close():
    t = {"now": 0.0}
    br = fallback.CircuitBreaker(
        cooldown=1.0, max_cooldown=8.0, clock=lambda: t["now"]
    )
    key = ("site", "pallas")
    assert br.admit(key) == "closed"
    br.trip(key)
    assert br.admit(key) is None  # open
    t["now"] = 1.1
    assert br.admit(key) == "probe"  # half-open: single probe admitted
    assert br.admit(key) is None  # second caller refused while probing
    br.record_success(key)  # probe succeeded
    assert br.admit(key) == "closed" and br.state(key) is None


def test_breaker_probe_failure_retrips_with_backoff():
    t = {"now": 0.0}
    br = fallback.CircuitBreaker(
        cooldown=1.0, max_cooldown=8.0, clock=lambda: t["now"]
    )
    key = ("site", "xla")
    br.trip(key)
    t["now"] = 1.1
    assert br.admit(key) == "probe"
    br.trip(key)  # probe failed: re-trip, cooldown doubles
    assert br.admit(key) is None
    t["now"] = 1.1 + 1.9
    assert br.admit(key) is None  # still inside the doubled window
    t["now"] = 1.1 + 2.1
    assert br.admit(key) == "probe"


def test_breaker_stranded_probe_expires():
    """A probe whose thread died must not strand the backend half-open."""
    t = {"now": 0.0}
    br = fallback.CircuitBreaker(cooldown=1.0, clock=lambda: t["now"])
    key = ("site", "pallas")
    br.trip(key)
    t["now"] = 1.1
    assert br.admit(key) == "probe"
    # the probe never reports back; after one base cooldown the slot frees
    t["now"] = 2.2
    assert br.admit(key) == "probe"


def test_run_chain_probe_gets_single_attempt():
    """A half-open probe gets exactly one attempt (no retry-once), so a
    still-broken backend costs one failure before falling through."""
    t = {"now": 0.0}
    br = fallback.CircuitBreaker(cooldown=1.0, clock=lambda: t["now"])
    calls = []

    def attempt(b):
        calls.append(b)
        if b == "xla":
            raise RuntimeError("xla down")
        return "ok"

    out, used = fallback.run_chain("s", "xla", attempt, breaker=br)
    assert used == "ref" and calls.count("xla") == 2  # closed: retry-once
    calls.clear()
    t["now"] = 1.1  # xla half-open now
    out, used = fallback.run_chain("s", "xla", attempt, breaker=br)
    assert used == "ref" and calls.count("xla") == 1  # probe: one attempt


def test_run_chain_probe_success_repromotes():
    t = {"now": 0.0}
    br = fallback.CircuitBreaker(cooldown=1.0, clock=lambda: t["now"])
    healthy = {"xla": False}

    def attempt(b):
        if b == "xla" and not healthy["xla"]:
            raise RuntimeError("down")
        return b

    out, used = fallback.run_chain("s2", "xla", attempt, breaker=br)
    assert used == "ref"
    healthy["xla"] = True
    t["now"] = 1.1
    out, used = fallback.run_chain("s2", "xla", attempt, breaker=br)
    assert used == "xla" and br.state(("s2", "xla")) is None


def test_breaker_thread_safety_smoke():
    """Concurrent admit/trip/record_success must not corrupt state."""
    br = fallback.CircuitBreaker(cooldown=1e-4)
    key = ("s", "b")
    errs = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(300):
                mode = br.admit(key)
                if mode and rng.random() < 0.5:
                    br.trip(key)
                elif mode:
                    br.record_success(key)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30.0)
    assert not errs


# ---------------------------------------------------------------------------
# validation messages name the offending indices
# ---------------------------------------------------------------------------


def test_edgebatch_nonfinite_weights_name_indices():
    w = np.ones(8, np.float32)
    w[2] = np.nan
    w[5] = np.inf
    with pytest.raises(ValueError, match=r"wgt: non-finite edge weights at "
                                         r"indices \[2, 5\]"):
        edgebatch.from_arrays(np.arange(8), np.arange(8), w)


def test_edgebatch_negative_ids_name_indices():
    src = np.arange(8)
    src[1] = -3
    src[4] = -7
    with pytest.raises(ValueError, match=r"src: negative vertex ids at "
                                         r"indices \[1, 4\].*-3"):
        edgebatch.from_arrays(src, np.arange(8))


def test_edgebatch_index_lists_truncate():
    w = np.full(16, np.nan, np.float32)
    with pytest.raises(ValueError, match=r"\(\+11 more\)"):
        edgebatch.from_arrays(np.arange(16), np.arange(16), w)


def test_edgebatch_length_mismatch_names_arrays():
    with pytest.raises(ValueError, match="wgt has 3 weights for 5 edges"):
        edgebatch.from_arrays(
            np.arange(5), np.arange(5), np.ones(3, np.float32)
        )


def test_updateplan_validation_names_indices():
    q_src = np.array([0, 1], np.int32)
    q_dst = np.array([1, 2], np.int32)
    q_wgt = np.array([1.0, np.nan], np.float32)
    q_del = np.array([False, False])
    with pytest.raises(ValueError, match=r"q_wgt at indices \[1\]"):
        updates.plan_from_canonical(q_src, q_dst, q_wgt, q_del).validate()


# ---------------------------------------------------------------------------
# faultinject leak guard plumbing
# ---------------------------------------------------------------------------


def test_faultinject_armed_introspection():
    assert faultinject.armed() == ()
    faultinject.arm("serve.enqueue", times=1)
    faultinject.arm("serve.seal", times=1)
    assert faultinject.armed() == ("serve.enqueue", "serve.seal")
    faultinject.disarm("serve.enqueue")
    assert faultinject.armed() == ("serve.seal",)
    faultinject.disarm()
    assert faultinject.armed() == ()


# ---------------------------------------------------------------------------
# host spans on the profiler's timeline, and the request timestamps
# ---------------------------------------------------------------------------

#: every program span of the served path with a WAL under a DiGraph
SERVED_SPANS = (
    "serve.dispatch.wait", "serve.walk.prepare", "serve.walk.device",
    "serve.walk.fetch", "serve.writer.wait", "durable.wal_append",
    "digraph.plan", "slot_update.dispatch", "serve.seal",
)


def _durable_round(base, state_dir):
    """Three update batches, each acked, then six walks, on a DiGraph
    behind a WAL; returns (walk tickets, update tickets)."""
    from repro.runtime import durable

    dg = durable.DurableGraph(
        REPRESENTATIONS["digraph"].from_csr(base),
        str(state_dir / "wal"), str(state_dir / "ckpt"), fsync=True,
    )
    srv = serve_mod.WalkServer(dg, batch_max=4).start()
    rng = np.random.default_rng(31)
    upds = []
    for _ in range(3):
        upds.append(srv.submit_update(make_plan(rng)))
        assert upds[-1].wait(60.0) and upds[-1].status == serve_mod.SERVED
    walks = [srv.submit_walk(rng.integers(0, N_V, 3), steps=3)
             for _ in range(6)]
    for t in walks:
        assert t.wait(60.0) and t.status == serve_mod.SERVED
    srv.stop()
    srv.assert_no_lost()
    dg.close()
    return walks, upds


def _host_event_names(trace_dir) -> set:
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(ev.name.split("#")[0] for ev in line.events)
    return names


def test_served_path_spans_reach_the_host_plane(base_csr, tmp_path):
    """A tiny served run under the profiler: every span of the served
    path is on the host plane, and the answers are bit-identical to the
    same run without the profiler (which also warms its programs)."""
    import jax

    plain, _ = _durable_round(base_csr, tmp_path / "plain")
    with jax.profiler.trace(str(tmp_path / "trace")):
        traced, upds = _durable_round(base_csr, tmp_path / "traced")
    names = _host_event_names(tmp_path / "trace")
    assert set(SERVED_SPANS) <= names, set(SERVED_SPANS) - names
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.visits, b.visits)
        assert a.generation == b.generation
    for t in traced:
        assert t.submitted_at <= t.taken_at <= t.done_at
        assert t.latency_s == t.done_at - t.submitted_at
    for t in upds:
        assert t.submitted_at <= t.taken_at <= t.applied_at <= t.acked_at


def test_ticket_timestamps_of_refusals_and_retries(base_csr):
    rep = REPRESENTATIONS["digraph"].from_csr(base_csr)
    # a retry sleeps 0.1-0.3 s before its request is taken again
    srv = serve_mod.WalkServer(rep, retry_backoff=0.2).start()
    bad = srv.submit_walk([N_V + 5], steps=2)
    assert bad.wait(30.0) and bad.status == serve_mod.REJECTED
    assert bad.submitted_at <= bad.taken_at <= bad.done_at
    faultinject.arm("serve.dispatch", times=1)
    t = srv.submit_walk([1, 2], steps=2)
    assert t.wait(60.0) and t.status == serve_mod.SERVED
    faultinject.disarm()
    assert t.attempts == 1 and t.taken_at <= t.done_at
    assert t.taken_at - t.submitted_at >= 0.1  # the retry's pop counts
    srv.stop()
    pending = serve_mod.WalkTicket([1], None, None, 2, None)
    assert pending.latency_s is None and pending.taken_at is None
    assert serve_mod.UpdateTicket(None).acked_at is None
    assert "ema_service_ms" not in srv.stats()


def test_dispatcher_holds_no_device_rows_between_batches(base_csr,
                                                       monkeypatch):
    """A served batch's ``[B, V]`` result is dropped once fetched, so the
    next batch does not run beside it on the device.  (On the CPU a
    fetched jax array stays alive through its numpy view, so the walk's
    result is wrapped in an object that copies when fetched.)"""
    import weakref

    class Rows:
        def __init__(self, a):
            self.a = np.asarray(a)

        def __array__(self, dtype=None, copy=None):
            return self.a.copy()

    made = []
    walk = walk_image.WalkImage.walk

    def tracked(self, *a, **kw):
        rows = Rows(walk(self, *a, **kw))
        made.append(weakref.ref(rows))
        return rows

    monkeypatch.setattr(walk_image.WalkImage, "walk", tracked)
    srv = serve_mod.WalkServer(
        REPRESENTATIONS["digraph"].from_csr(base_csr)).start()
    t = srv.submit_walk([1, 2], steps=2)
    assert t.wait(60.0) and t.status == serve_mod.SERVED
    time.sleep(0.2)  # the dispatcher is back waiting for a request
    assert made and all(r() is None for r in made)
    srv.stop()
