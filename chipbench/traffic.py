"""The one traffic generator: open-loop arrivals read from a mix file.

A mix (``traffic/<name>.json``) gives:

- ``walk_rate``: walks per second.  Each walk has ``walk.steps`` steps
  from ``walk.seeds`` seed vertices, drawn uniformly from the vertices
  with edges, each with a float32 weight drawn uniformly from
  ``walk.seed_weights``;
- ``ingest_rate``: update batches per second.  A batch holds
  ``ingest.batch_fraction`` of |E| edge ops (or ``ingest.batch_edges``),
  an ``ingest.insert_share`` of them inserts with endpoints from the
  configuration's own generator, the rest deletes of distinct live base
  edges;
- ``check_share``: the share of answered walks compared with the
  reference, drawn from the seed, at most ``check_max`` of them;
- ``warmup_batches``: update batches sent one by one before the window,
  so that the update programs' shapes compile in set-up.  The warm-up's
  walks and batches come from a stream that no seed changes, so every
  seed warms the same way;
- ``drain_s``: how long past the window's end the answers still out are
  waited for; the server then stops and refuses what is still queued.

Arrivals are open loop: ``rate * seconds`` requests per stream, the
first due at the window's start, their gaps one fixed set (exponential,
mean ``1 / rate``) that each seed puts in another order, so every seed
offers the same load.  The whole request
stream is drawn from the seed before the window.  Every request is timed
on the client's side from when it was due to when its answer or ack is
seen.  The program sees only the requests.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from chipbench import reference

#: how long set-up waits for a warm-up request
WARM_WAIT_S = 600.0
#: the warm-up's random stream, the same for every seed
WARM_STREAM = (0, 0)
#: how often the collector looks at outstanding tickets
POLL_S = 0.001


@dataclass
class WalkRecord:
    seeds: np.ndarray
    weights: np.ndarray
    due: float
    keep: bool = False
    done: float = float("nan")
    status: str = "pending"
    generation: int = -1
    #: the highest generation any ack had named when this walk was sent
    acked_gen_at_send: int = -1
    visits: Optional[np.ndarray] = None


@dataclass
class UpdateRecord:
    order: int
    ins: tuple
    dels: tuple
    n_ops: int
    due: float
    done: float = float("nan")
    status: str = "pending"
    generation: int = -1


@dataclass
class Log:
    walks: list = field(default_factory=list)
    updates: list = field(default_factory=list)


def arrival_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """``round(rate * seconds)`` arrival offsets in [0, seconds), the
    first at 0: one fixed set of exponential gaps, scaled to the window,
    in a seed's order."""
    n = int(round(rate * seconds))
    if n <= 0:
        return np.zeros(0)
    gaps = np.random.default_rng(0).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng([seed, 77]).permutation(gaps)
    return np.concatenate(([0.0], np.cumsum(gaps[:-1])))


class Traffic:
    """Drives a server with the mix through three callables:
    ``submit_walk(seeds, weights, steps) -> ticket``, ``make_plan(ins,
    dels) -> plan`` and ``submit_update(plan) -> ticket``.

    ``ins`` is ``(src, dst, wgt)`` and ``dels`` is ``(src, dst)``, numpy
    arrays; a ticket has ``done``, ``wait(timeout)``, ``status``,
    ``generation`` and, for a walk, ``visits``.
    """

    def __init__(self, mix: dict, cfg: dict, csr, generator, extras: dict,
                 seed: int, submit_walk: Callable, make_plan: Callable,
                 submit_update: Callable):
        self.mix, self.cfg, self.csr = mix, cfg, csr
        self.gen, self.extras = generator, extras
        self.seed = int(seed)
        self.submit_walk_fn = submit_walk
        self.make_plan_fn, self.submit_update_fn = make_plan, submit_update
        self.walk = mix["walk"]
        self.ingest = mix.get("ingest", {})
        self.with_edges = np.flatnonzero(np.diff(csr.offsets) > 0)
        self._deleted: set = set()
        self._order = 0
        self._acked_gen = -1
        self._out: list = []
        self._lock = threading.Lock()
        self.log = Log()
        self.warm = Log()

    # -- requests ---------------------------------------------------------
    def _rng(self, *stream) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def walk_request(self, rng: np.random.Generator):
        """Seed vertices and their float32 weights for one walk."""
        k = int(self.walk["seeds"])
        lo, hi = self.walk.get("seed_weights", (1.0, 1.0))
        return (rng.choice(self.with_edges, k),
                rng.uniform(lo, hi, k).astype(np.float32))

    def batch_size(self) -> int:
        ing = self.ingest
        if "batch_edges" in ing:
            return int(ing["batch_edges"])
        return max(int(round(self.csr.m * float(ing["batch_fraction"]))), 2)

    def make_batch(self, rng: np.random.Generator):
        """One batch of raw inserts and deletes, the deletes of distinct
        live base edges that no earlier batch deleted."""
        size = self.batch_size()
        n_ins = int(round(size * float(self.ingest.get("insert_share", 0.5))))
        n_del = size - n_ins
        src, dst = self.gen.sample_edges(self.cfg, self.extras, rng, 2 * n_ins + 8)
        keep = src != dst
        src, dst = src[keep][:n_ins], dst[keep][:n_ins]
        wgt = rng.uniform(0.0, 1.0, src.shape[0]).astype(np.float32) + np.float32(1e-3)
        pick = np.unique(rng.integers(0, self.csr.m, 2 * n_del + 8))
        rng.shuffle(pick)
        d_src = np.searchsorted(self.csr.offsets, pick, side="right") - 1
        d_dst = self.csr.dst[pick].astype(np.int64)
        keys = reference.keys_of(d_src, d_dst)
        fresh = np.array([k not in self._deleted for k in keys.tolist()], bool)
        keys, d_src, d_dst = (a[fresh][:n_del] for a in (keys, d_src, d_dst))
        self._deleted.update(keys.tolist())
        return (src, dst, wgt), (d_src, d_dst)

    def _update_record(self, ins, dels, due: float) -> UpdateRecord:
        n_ops = reference.canonical_ops(*ins, *dels)[0].shape[0]
        rec = UpdateRecord(self._order, ins, dels, n_ops, due)
        self._order += 1
        return rec

    def _settle(self, rec, t, now: float) -> None:
        rec.done = now
        rec.status = t.status
        rec.generation = -1 if t.generation is None else int(t.generation)
        if isinstance(rec, UpdateRecord) and rec.status == "served":
            self._acked_gen = max(self._acked_gen, rec.generation)
        if isinstance(rec, WalkRecord) and rec.status == "served" and rec.keep:
            rec.visits = np.array(t.visits)

    # -- set-up -----------------------------------------------------------
    def warmup(self, walks: int) -> None:
        """``walks`` walks sent at once, then ``warmup_batches`` update
        batches one after another, each waited for; drawn alike for
        every seed."""
        rng = np.random.default_rng(WARM_STREAM)
        sent = []
        for _ in range(walks):
            seeds, weights = self.walk_request(rng)
            sent.append(self.submit_walk_fn(seeds, weights, int(self.walk["steps"])))
        for t in sent:
            t.wait(WARM_WAIT_S)
        for _ in range(int(self.mix.get("warmup_batches", 0))):
            ins, dels = self.make_batch(rng)
            rec = self._update_record(ins, dels, time.perf_counter())
            t = self.submit_update_fn(self.make_plan_fn(ins, dels))
            t.wait(WARM_WAIT_S)
            self._settle(rec, t, time.perf_counter())
            self.warm.updates.append(rec)

    # -- the window -------------------------------------------------------
    def plan(self, seconds: float) -> list:
        """The window's requests, drawn from the seed: ``(offset, kind,
        payload)`` in the order they are due."""
        reqs = []
        rng = self._rng(1)
        share = float(self.mix.get("check_share", 1.0))
        for off in arrival_offsets(float(self.mix.get("walk_rate", 0)),
                                   seconds, self.seed):
            seeds, weights = self.walk_request(rng)
            reqs.append((off, "walk", (seeds, weights, rng.random() < share)))
        rng = self._rng(2)
        for off in arrival_offsets(float(self.mix.get("ingest_rate", 0)),
                                   seconds, self.seed + 1):
            reqs.append((off, "update", self.make_batch(rng)))
        reqs.sort(key=lambda r: r[0])
        return reqs

    def run(self, seconds: float, reqs: list, drain_s: float) -> tuple:
        """The measured window: sends each request of ``reqs`` when it is
        due, then waits up to ``drain_s`` past the window's end for the
        answers still out.  Returns (start, end) on the client clock."""
        steps = int(self.walk["steps"])
        out, lock = self._out, self._lock
        sending = threading.Event()
        sending.set()
        start = time.perf_counter()
        end = start + float(seconds)

        def collect():
            while sending.is_set() or out:
                now = time.perf_counter()
                with lock:
                    ready = [(t, r) for t, r in out if t.done]
                    if ready:
                        out[:] = [(t, r) for t, r in out if not t.done]
                for t, r in ready:
                    self._settle(r, t, now)
                if not sending.is_set() and now > end + drain_s:
                    return
                time.sleep(POLL_S)

        collector = threading.Thread(target=collect, name="chipbench-collect")
        collector.start()
        try:
            for off, kind, payload in reqs:
                due = start + off
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if kind == "walk":
                    seeds, weights, keep = payload
                    rec = WalkRecord(seeds, weights, due, keep,
                                     acked_gen_at_send=self._acked_gen)
                    t = self.submit_walk_fn(seeds, weights, steps)
                    self.log.walks.append(rec)
                else:
                    rec = self._update_record(*payload, due)
                    t = self.submit_update_fn(self.make_plan_fn(*payload))
                    self.log.updates.append(rec)
                with lock:
                    out.append((t, rec))
            wait = end - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
        finally:
            sending.clear()
            collector.join()
        return start, end

    def settle_rest(self) -> None:
        """After the server stopped: settle every request still out, as
        its ticket ended (a refusal of what was still queued), or as
        ``pending`` where it never resolved."""
        now = time.perf_counter()
        with self._lock:
            rest, self._out[:] = list(self._out), []
        for t, r in rest:
            if t.done:
                self._settle(r, t, now)
