"""Graph walk serving launcher (DESIGN.md §16) — the serving front-end CLI.

Retires the seed's LM-decode launcher: the graph engine IS the product
now, and this entry point drives the multi-tenant ``runtime.serve``
WalkServer against a synthetic graph under mixed update/walk traffic,
printing latency percentiles and the zero-lost / torn-read proof fields.

  PYTHONPATH=src python -m repro.launch.serve --rep digraph --scale 10 \\
      --requests 400 --update-every 10 --verify 0.25

Besides ``main``, this module hosts the *shared* traffic machinery the
bench suite and the serve tests reuse:

* :func:`build_rep` — synthetic graph → representation instance;
* :func:`run_traffic` — the mixed walk/update submission loop;
* :class:`GenerationOracle` — a host edge-set replayed one sealed
  generation at a time, walking each with numpy; the torn-read check
  (:func:`count_torn_reads`) proves every served walk matches the
  oracle *for its own generation* — the snapshot-isolation contract.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..core import REPRESENTATIONS, edgebatch, updates
from ..io import synthetic
from ..runtime import serve as serve_mod
from .cache import enable_compile_cache


def build_rep(rep: str = "digraph", *, kind: str = "web", scale: int = 10,
              edge_factor: int = 8, seed: int = 7):
    """Synthetic graph → (representation, base CSR)."""
    csr = synthetic.make_graph(
        kind, scale=scale, edge_factor=edge_factor, seed=seed, weighted=True
    )
    return REPRESENTATIONS[rep].from_csr(csr), csr


def seed_visits_row(nv: int, seeds, weights=None) -> np.ndarray:
    """The [nv] initial visit vector a seed list denotes (matches the
    server's dispatch-side materialization)."""
    row = np.zeros(nv, np.float32)
    seeds = np.atleast_1d(np.asarray(seeds, np.int64))
    w = (
        np.ones(seeds.shape[0], np.float32)
        if weights is None
        else np.asarray(weights, np.float32).reshape(-1)
    )
    np.add.at(row, seeds, w)
    return row


def _find(keys: np.ndarray, q: np.ndarray):
    """Insertion points of ``q`` in sorted ``keys``, and which are present."""
    at = np.searchsorted(keys, q)
    hit = at < keys.shape[0]
    hit[hit] = keys[at[hit]] == q[hit]
    return at, hit


class GenerationOracle:
    """Host replica of the served graph, one sealed generation at a time.

    Updates are recorded against the generation that first exposed them
    (the ack's ``ticket.generation``); ``walk(gen, row, steps)`` advances
    the edge-set replica to exactly that generation and walks it with
    numpy (visits1[u] = Σ_{(u,v)∈E} visits0[v], weights don't enter the
    count walk).  Verification must proceed in nondecreasing generation
    order — the torn-read check sorts served tickets by generation.

    The edge set is a sorted int64 array of ``src << 32 | dst`` keys, so
    a graph of 10^8 edges costs 8 bytes per edge: plans apply by binary
    search and one delete/insert copy, and a walk step is one gather of
    the visits at every edge's ``dst`` and one ``np.add.reduceat`` over
    the sources' runs (~30% faster than ``np.bincount`` at 1.6e7 edges).
    ``walk_many`` walks the k rows of one generation together.
    """

    def __init__(self, csr):
        off = np.asarray(csr.offsets, np.int64)
        self.nv = int(csr.n)
        m = int(csr.m)
        rows = np.repeat(np.arange(self.nv, dtype=np.int64), np.diff(off))
        keys = (rows << 32) | np.asarray(csr.dst)[:m].astype(np.int64)
        if keys.shape[0] > 1 and not bool((keys[1:] > keys[:-1]).all()):
            keys = np.unique(keys)
        self._keys = keys
        self._gen = 0
        self._plans: dict = {}
        self._arrays = None

    def record(self, gen: int, plan) -> None:
        """Register ``plan`` as first visible at sealed generation ``gen``."""
        self._plans.setdefault(int(gen), []).append(plan)

    def _apply(self, plan) -> None:
        # canonical op stream: each (src, dst) appears once, so apply
        # order within a plan doesn't matter
        k = (plan.q_src.astype(np.int64) << 32) | plan.q_dst.astype(np.int64)
        rm = np.asarray(plan.q_del, bool)
        at, hit = _find(self._keys, k[rm])
        keys = np.delete(self._keys, at[hit])
        new = np.unique(k[~rm])
        at, hit = _find(keys, new)
        self._keys = np.insert(keys, at[~hit], new[~hit])

    def _advance(self, gen: int) -> None:
        if gen < self._gen:
            raise ValueError(
                f"oracle at generation {self._gen}, asked to rewind to {gen}"
            )
        while self._gen < gen:
            self._gen += 1
            for plan in self._plans.pop(self._gen, ()):
                self._apply(plan)
            self._arrays = None

    def walk(self, gen: int, visits_row: np.ndarray, steps: int,
             *, drop_rows=None) -> np.ndarray:
        """Oracle walk at ``gen``; ``drop_rows`` models degraded coverage.

        A quarantined shard's rows are masked out of the sharded walk
        (their lo/hi read zero-length), so their accumulations vanish at
        EVERY step while edges from healthy rows into them still read
        the visit vector — exactly ``nxt[drop_rows] = 0`` per step
        (§17).  ``drop_rows=None`` (or empty) is the full-coverage walk.
        """
        return self.walk_many(
            gen, np.asarray(visits_row)[None], steps, drop_rows=drop_rows
        )[0]

    def walk_many(self, gen: int, visits_rows: np.ndarray, steps: int,
                  *, drop_rows=None) -> np.ndarray:
        """:meth:`walk` for a [k, nv] stack of visit rows at once."""
        self._advance(int(gen))
        if self._arrays is None:
            s = self._keys >> 32
            heads = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])[: s.shape[0]]
            self._arrays = (s[heads], heads, self._keys & 0xFFFFFFFF)
        srcs, heads, d = self._arrays
        drop = (
            None if drop_rows is None or len(drop_rows) == 0
            else np.asarray(drop_rows, np.int64)
        )
        v = np.asarray(visits_rows, np.float64)
        for _ in range(steps):
            nxt = np.zeros_like(v)
            if heads.shape[0]:
                for j in range(v.shape[0]):
                    nxt[j, srcs] = np.add.reduceat(v[j][d], heads)
            if drop is not None:
                nxt[:, drop] = 0.0
            v = nxt
        return v


def run_traffic(
    server: "serve_mod.WalkServer",
    nv: int,
    *,
    requests: int = 200,
    steps: int = 4,
    seeds_per_request: int = 4,
    update_every: int = 10,
    update_size: int = 256,
    delete_every: int = 4,
    seed: int = 0,
    submit_gap_s: float = 0.0,
    timeout=None,
):
    """Drive a mixed update/walk stream through a running server.

    Every ``update_every``-th request is preceded by an update batch
    (every ``delete_every``-th of those deletes random pairs instead of
    inserting).  Returns ``(walk_tickets, update_tickets)`` where each
    update ticket is paired with its plan for oracle replay.  Tickets
    are NOT waited on here — callers decide how long to block.
    """
    rng = np.random.default_rng(seed)
    walk_tickets, update_tickets = [], []
    n_updates = 0
    for i in range(int(requests)):
        if update_every and i % update_every == 0:
            if delete_every and n_updates % delete_every == delete_every - 1:
                eb = edgebatch.from_arrays(
                    rng.integers(0, nv, update_size),
                    rng.integers(0, nv, update_size),
                )
                plan = updates.plan_update(deletes=eb)
            else:
                eb = edgebatch.random_insertions(rng, nv, update_size)
                plan = updates.plan_update(inserts=eb)
            update_tickets.append((server.submit_update(plan), plan))
            n_updates += 1
        seeds = rng.integers(0, nv, size=seeds_per_request)
        walk_tickets.append(
            server.submit_walk(seeds, steps=steps, timeout=timeout)
        )
        if submit_gap_s:
            time.sleep(submit_gap_s)
    return walk_tickets, update_tickets


def count_torn_reads(
    oracle: GenerationOracle,
    walk_tickets,
    update_tickets,
    *,
    sample: float = 1.0,
    seed: int = 0,
    rtol: float = 1e-4,
    atol: float = 1e-2,
    down_rows_of=None,
):
    """Verify served walks against the per-generation oracle.

    Returns ``(torn, checked)``: ``torn`` counts served walks whose
    visits do NOT match the oracle at their own generation — any torn
    read (a walk that saw a half-applied plan) fails the allclose, since
    no sealed edge-set produces its numbers.  ``sample`` < 1 checks a
    random subset (bench runs on larger graphs bound verify cost; tests
    use 1.0).  ``down_rows_of`` (ticket → row-id array or None) maps a
    degraded response's ``down_shards`` to the masked rows so §17
    coverage-degraded answers verify against the SAME oracle — a
    degraded walk is still exact on the part it claims to cover.
    """
    rng = np.random.default_rng(seed)
    for t, plan in update_tickets:
        if t.status == serve_mod.SERVED:
            oracle.record(t.generation, plan)
    served = sorted(
        (t for t in walk_tickets if t.status == serve_mod.SERVED),
        key=lambda t: t.generation,
    )
    # walks that share (generation, steps, masked rows) go through the
    # oracle together, one pass over its edges per step
    groups: dict = {}
    checked = 0
    for t in served:
        if sample < 1.0 and rng.random() > sample:
            continue
        drop = None if down_rows_of is None else down_rows_of(t)
        if drop is not None and len(drop) == 0:
            drop = None
        key = (t.generation, t.steps,
               None if drop is None else np.asarray(drop, np.int64).tobytes())
        groups.setdefault(key, (drop, []))[1].append(t)
        checked += 1
    torn = 0
    for (gen, steps, _), (drop, ts) in sorted(
        groups.items(), key=lambda kv: kv[0][:2]
    ):
        rows = np.stack([
            np.asarray(t.visits_row, np.float32)
            if t.visits_row is not None
            else seed_visits_row(oracle.nv, t.seeds, t.weights)
            for t in ts
        ])
        expect = oracle.walk_many(gen, rows, steps, drop_rows=drop)
        for t, e in zip(ts, expect):
            if not np.allclose(np.asarray(t.visits, np.float64), e,
                               rtol=rtol, atol=atol):
                torn += 1
    return torn, checked


def percentiles(latencies_s, qs=(50, 95, 99)) -> dict:
    """{"p50_ms": ..., ...} from a list of per-request latencies."""
    if not latencies_s:
        return {f"p{q}_ms": float("nan") for q in qs}
    arr = np.asarray(latencies_s, np.float64) * 1e3
    return {f"p{q}_ms": float(np.percentile(arr, q)) for q in qs}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="serve mixed walk/update traffic from a WalkServer"
    )
    ap.add_argument("--rep", default="digraph", choices=sorted(REPRESENTATIONS))
    ap.add_argument("--kind", default="web")
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--requests", type=int, default=400)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--update-every", type=int, default=10)
    ap.add_argument("--update-size", type=int, default=256)
    ap.add_argument("--batch-max", type=int, default=32)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--timeout", type=float, default=None,
                    help="per-request deadline in seconds")
    ap.add_argument("--backend", default="auto",
                    help="slot_walk backend request (auto/pallas/xla/ref)")
    ap.add_argument("--verify", type=float, default=0.25,
                    help="fraction of served walks checked against the "
                         "per-generation oracle (0 disables)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    rep, csr = build_rep(
        args.rep, kind=args.kind, scale=args.scale,
        edge_factor=args.edge_factor,
    )
    nv = int(csr.n)
    print(f"[serve] {args.rep} kind={args.kind} |V|={nv} |E|={int(csr.m)}")
    server = serve_mod.WalkServer(
        rep, max_queue=args.max_queue, batch_max=args.batch_max,
        default_timeout=args.timeout, walk_backend=args.backend,
    ).start()
    t0 = time.monotonic()
    walks, upds = run_traffic(
        server, nv, requests=args.requests, steps=args.steps,
        update_every=args.update_every, update_size=args.update_size,
        seed=args.seed, timeout=args.timeout,
    )
    for t in walks:
        t.wait(60.0)
    stats = server.stop()
    dt = time.monotonic() - t0
    server.assert_no_lost()

    lat = [t.latency_s for t in walks if t.status == serve_mod.SERVED]
    pct = percentiles(lat)
    torn = checked = 0
    if args.verify > 0:
        torn, checked = count_torn_reads(
            GenerationOracle(csr), walks, upds, sample=args.verify
        )
    print(
        f"[serve] {stats['served']}/{stats['submitted']} served in {dt:.2f}s "
        f"({stats['served'] / max(dt, 1e-9):.1f} req/s), "
        f"shed={stats['shed_expired']} "
        f"rejected={stats['rejected_backpressure'] + stats['rejected_other']} "
        f"failed={stats['failed']}"
    )
    print(
        f"[serve] latency p50={pct['p50_ms']:.2f}ms p95={pct['p95_ms']:.2f}ms "
        f"p99={pct['p99_ms']:.2f}ms | generations={stats['generation'] + 1} "
        f"updates={stats['updates_applied']} "
        f"fallbacks={stats['breaker_fallbacks']}"
    )
    if checked:
        print(f"[serve] torn_reads={torn}/{checked} checked")
        assert torn == 0, "snapshot isolation violated"
    return stats


if __name__ == "__main__":
    main()
