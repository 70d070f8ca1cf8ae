"""Overload-safe multi-tenant walk serving front-end (DESIGN.md §16).

The ROADMAP's production-traffic item: an async request queue in front of
the walk engine, turning concurrent per-user walk requests into the
single batched ``[B, V]`` dispatches PR 4/5 made fast, with a robustness
contract stronger than the sum of its parts:

* **Snapshot isolation** — one writer thread applies ``UpdatePlan``s to
  the live representation and *seals* an immutable ``WalkImage``
  generation after each group (``core.walk_image.seal_generation``,
  riding the per-buffer COW of §10).  Readers always walk the last
  sealed generation: a reader can never observe a half-applied plan,
  because generations are frozen images and the writer's subsequent
  patches copy-on-write instead of donating shared buffers.  Every
  response carries its ``generation`` id, so consistency is *checkable*
  (the bench and the hypothesis sweep verify bit-parity against a dense
  oracle per generation — ``torn_reads == 0``).

* **Admission control + backpressure** — both queues are bounded.  A
  walk submitted past ``max_queue`` depth is rejected immediately with a
  ``Retry-After``-style hint (``RejectedError.retry_after``, estimated
  from the EMA per-request service time); a request whose deadline
  expired while it waited is shed before dispatch (load shedding: the
  batch never pays for work nobody is waiting for).

* **Graceful degradation** — walk dispatches run through the
  ``kernels/fallback`` breaker chain (pallas → xla → ref), so a tripped
  backend degrades throughput instead of failing requests; serve-level
  transient failures get bounded retry with backoff
  (``dispatch_retries``), and only an exhausted chain fails a ticket —
  visibly, never silently.

* **Fault-injected audits** — ``faultinject`` points at the three
  boundary transitions (``serve.enqueue``, ``serve.seal``,
  ``serve.dispatch``) prove the zero-lost contract: every submitted
  ticket resolves as served / rejected / failed (``assert_no_lost``),
  and a failed seal keeps readers on the previous consistent generation
  while the writer retries.

* **Shard failover (DESIGN.md §17)** — serving a ``ShardedGraph``, a
  single lost shard degrades coverage instead of availability.  A walk
  dispatch that trips ``ShardFaultError(sid)`` queues that shard for
  quarantine on the writer (``_pending_quarantine``) and retries the
  batch — against the previous sealed generation first, then against
  the degraded reseal once the writer flips it.  Every response carries
  ``coverage`` (fraction of the vertex space served) and
  ``down_shards`` so a degraded answer is *explicit*, never silent.
  The writer optionally paces a round-robin integrity audit
  (``audit_every`` > 0 → one ``failover.AuditScheduler`` tick per N
  writer rounds) to catch *silent* corruption on the live rep before it
  can reach a sealed generation; ``run_on_writer`` executes admin work
  (chaos injection, ``rebuild_shard`` reintegration) on the writer
  thread, serialized with applies, with an optional reseal after.

* **Host spans** — both threads mark their host work with
  ``jax.profiler.TraceAnnotation``, so a profiler trace puts it on the
  device's timeline: the dispatcher's ``serve.dispatch.wait`` (no walk
  queued), ``serve.walk.prepare`` (shedding, rows, the ``[B, V]``
  stack, the program's enqueue), ``serve.walk.device`` (waiting for
  it) and ``serve.walk.fetch`` (device to host, resolving the
  tickets); the writer's ``serve.writer.wait`` and ``serve.seal``.
  The apply path adds ``durable.wal_append``, ``digraph.plan`` and
  ``slot_update.dispatch``.  With no profiler running a span costs one
  inactive TraceMe.  Tickets carry ``submitted_at``, ``taken_at`` and
  ``done_at`` (updates also ``applied_at`` and ``acked_at``).

The server is representation-agnostic: anything exposing
``apply(plan)`` (returning ``(rep, dm)`` or mutating in place) plus
either ``to_walk_image()`` or its own ``seal_generation`` (all five
single-device representations, and ``ShardedGraph`` across a mesh)
serves.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Optional

import jax
import numpy as np

from ..core import alloc, updates, walk_image
from ..kernels import fallback as _fb
from ..kernels.slot_walk import ops as _sw_ops
from . import faultinject

#: Ticket terminal states.  "pending" is the only non-terminal one.
SERVED, REJECTED, FAILED, PENDING = "served", "rejected", "failed", "pending"


class RejectedError(RuntimeError):
    """A request the server declined cleanly (never started).

    ``reason`` is one of the admission reasons ("backpressure",
    "expired", "shutdown", "enqueue_fault", "seed_out_of_range",
    "shape_mismatch"); ``retry_after`` (seconds, backpressure only) is
    the Retry-After hint — the estimated time for the queue to drain
    below the watermark.
    """

    def __init__(self, reason: str, retry_after: Optional[float] = None):
        msg = f"request rejected: {reason}"
        if retry_after is not None:
            msg += f" (retry after {retry_after * 1e3:.1f}ms)"
        super().__init__(msg)
        self.reason = reason
        self.retry_after = retry_after


@dataclasses.dataclass
class Generation:
    """One sealed, immutable walk image plus its bookkeeping."""

    gen_id: int
    image: walk_image.WalkImage
    #: updates applied to the live rep when this generation sealed
    seq: int


class _Ticket:
    """Base request handle: threading.Event + terminal outcome.

    ``submitted_at``, ``taken_at`` (last popped off its queue by a server
    thread, so a retried walk counts its last wait) and ``done_at`` (its
    outcome set) are ``time.monotonic()`` readings, meaningful only as
    differences; ``taken_at`` stays None for a request never taken.
    """

    __slots__ = (
        "status", "reason", "retry_after", "error", "generation",
        "submitted_at", "taken_at", "done_at", "_done",
    )

    def __init__(self):
        self.status = PENDING
        self.reason: Optional[str] = None
        self.retry_after: Optional[float] = None
        self.error: Optional[BaseException] = None
        self.generation: Optional[int] = None
        self.submitted_at = time.monotonic()
        self.taken_at: Optional[float] = None
        self.done_at: Optional[float] = None
        self._done = threading.Event()

    # -- resolution (server side) ---------------------------------------
    def _resolve(self, status: str) -> None:
        self.status = status
        self.done_at = time.monotonic()
        self._done.set()

    def _reject(self, reason: str, retry_after: Optional[float] = None):
        self.reason = reason
        self.retry_after = retry_after
        self._resolve(REJECTED)
        return self

    def _fail(self, err: BaseException):
        self.error = err
        self._resolve(FAILED)
        return self

    # -- caller side -----------------------------------------------------
    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def _raise_terminal(self):
        if self.status == REJECTED:
            raise RejectedError(self.reason or "rejected", self.retry_after)
        if self.status == FAILED:
            raise RuntimeError("request failed") from self.error


class WalkTicket(_Ticket):
    """Handle for one walk request; ``result()`` blocks for the visits.

    ``coverage``/``down_shards`` describe the serving generation the
    response was computed on: 1.0 and ``()`` for a healthy mesh (or any
    single-device image); < 1.0 names the degraded fraction and the
    quarantined shard ids whose rows read as zero (§17).
    """

    __slots__ = ("seeds", "weights", "visits_row", "steps", "deadline",
                 "attempts", "visits", "coverage", "down_shards")

    def __init__(self, seeds, weights, visits_row, steps, deadline):
        super().__init__()
        self.seeds = seeds
        self.weights = weights
        self.visits_row = visits_row
        self.steps = int(steps)
        self.deadline = deadline
        self.attempts = 0
        self.visits: Optional[np.ndarray] = None
        self.coverage: Optional[float] = None
        self.down_shards: tuple = ()

    @property
    def latency_s(self) -> Optional[float]:
        """Seconds from submission to the outcome; None while pending."""
        if self.done_at is None:
            return None
        return self.done_at - self.submitted_at

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self.wait(timeout):
            raise TimeoutError("walk ticket still pending")
        self._raise_terminal()
        return self.visits


class UpdateTicket(_Ticket):
    """Handle for one update; acked only once a sealed generation holds it.

    ``applied_at`` is when the writer finished applying it to the live
    representation; ``acked_at`` when a sealed generation acked it.
    """

    __slots__ = ("plan", "dm", "applied_at")

    def __init__(self, plan):
        super().__init__()
        self.plan = plan
        self.dm: Optional[int] = None
        self.applied_at: Optional[float] = None

    @property
    def acked_at(self) -> Optional[float]:
        return self.done_at if self.status == SERVED else None

    def result(self, timeout: Optional[float] = None) -> int:
        """Blocks until the update is visible to readers; returns ΔM."""
        if not self.wait(timeout):
            raise TimeoutError("update ticket still pending")
        self._raise_terminal()
        return self.dm


class AdminTicket(_Ticket):
    """Handle for one writer-thread admin op (``run_on_writer``).

    The callable runs on the writer thread — serialized with applies and
    seals, so it may safely mutate the live representation (quarantine,
    ``rebuild_shard`` reintegration, chaos corruption).  It is NOT part
    of the zero-lost walk/update ledgers; ``admin_ops`` counts it.
    """

    __slots__ = ("fn", "reseal", "value")

    def __init__(self, fn, reseal: bool):
        super().__init__()
        self.fn = fn
        self.reseal = bool(reseal)
        self.value = None

    def result(self, timeout: Optional[float] = None):
        if not self.wait(timeout):
            raise TimeoutError("admin ticket still pending")
        self._raise_terminal()
        return self.value


def _fresh_stats() -> dict:
    return {
        # walk-side accounting (the zero-lost ledger)
        "submitted": 0, "served": 0, "shed_expired": 0,
        "rejected_backpressure": 0, "rejected_other": 0, "failed": 0,
        # update side
        "updates_submitted": 0, "updates_applied": 0, "updates_failed": 0,
        "updates_rejected": 0,
        # engine health
        "seals": 0, "seal_failures": 0, "batches": 0, "max_batch": 0,
        "dispatch_retries": 0, "breaker_fallbacks": 0,
        # walk steps answered by the sparse frontier form or the dense scan
        "walk_steps_sparse": 0, "walk_steps_dense": 0,
        # shard failover (§17)
        "shard_quarantines": 0, "audit_detections": 0,
        "served_degraded": 0, "admin_ops": 0,
    }


class WalkServer:
    """Batched, snapshot-isolated, overload-safe walk service (§16).

    One writer thread owns the live representation; one dispatcher
    thread drains the walk queue into coalesced ``[B, V]`` batched
    dispatches against the last sealed generation.  All tuning knobs
    are constructor arguments so tests can drive every regime:

    ``max_queue``        walk admission bound (backpressure watermark)
    ``batch_max``        max requests coalesced into one dispatch
    ``default_timeout``  per-request deadline when the caller gives none
                         (None = no deadline)
    ``dispatch_retries`` serve-level retries of a failed batch dispatch
    ``retry_backoff``    base seconds of the retry backoff (attempt 1)
    ``retry_max_backoff`` ceiling of the exponential retry backoff
    ``update_queue_max`` update admission bound
    ``seal_group_max``   updates coalesced under one seal
    ``walk_backend``     slot_walk backend request ("auto" → device)
    ``audit_every``      writer rounds between AuditScheduler ticks
                         (0 = no background integrity audits)
    """

    def __init__(
        self,
        rep,
        *,
        max_queue: int = 256,
        batch_max: int = 32,
        default_timeout: Optional[float] = None,
        dispatch_retries: int = 2,
        retry_backoff: float = 0.002,
        retry_max_backoff: float = 0.25,
        update_queue_max: int = 64,
        seal_group_max: int = 8,
        walk_backend: str = "auto",
        audit_every: int = 0,
    ):
        self._rep = rep
        self.max_queue = int(max_queue)
        self.batch_max = int(batch_max)
        self.default_timeout = default_timeout
        self.dispatch_retries = int(dispatch_retries)
        self.retry_backoff = float(retry_backoff)
        self.retry_max_backoff = float(retry_max_backoff)
        self.update_queue_max = int(update_queue_max)
        self.seal_group_max = int(seal_group_max)
        self.walk_backend = walk_backend
        self.audit_every = int(audit_every)

        self._lock = threading.Lock()
        self._walk_cv = threading.Condition(self._lock)
        self._upd_cv = threading.Condition(self._lock)
        self._walk_q: collections.deque = collections.deque()
        self._upd_q: collections.deque = collections.deque()
        self._stats = _fresh_stats()
        self._ema_service_s = 1e-3  # per-request EMA, seeded optimistically
        self._generation: Optional[Generation] = None
        self._gen_counter = 0
        self._seq = 0  # updates applied to the live rep
        self._seal_pending: list = []  # applied updates awaiting a seal ack
        self._closed = False
        self._threads: list[threading.Thread] = []
        # §17 failover control plane (writer-owned except the queues)
        self._admin_q: collections.deque = collections.deque()
        self._pending_quarantine: set = set()
        self._auditor = None  # lazy failover.AuditScheduler
        self._known_down: set = set()
        self._rng = np.random.default_rng(0x5EED)  # retry jitter only

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> "WalkServer":
        """Seal generation 0 and start the writer + dispatcher threads."""
        if self._threads:
            raise RuntimeError("server already started")
        self._known_down = set(getattr(self._base_rep(), "down", ()) or ())
        self._seal_locked(initial=True)
        self._closed = False
        for name, fn in (("serve-writer", self._writer_loop),
                         ("serve-dispatch", self._dispatch_loop)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self, *, drain: bool = True, timeout: float = 30.0) -> dict:
        """Stop accepting requests; drain (or reject) the queues; join.

        With ``drain=True`` both threads finish everything already
        admitted before exiting — in-flight requests are never dropped.
        Returns the final stats dict.
        """
        with self._lock:
            self._closed = True
            if not drain:
                while self._walk_q:
                    self._resolve_reject(
                        self._walk_q.popleft(), "shutdown", walk=True
                    )
                while self._upd_q:
                    self._resolve_reject(
                        self._upd_q.popleft(), "shutdown", walk=False
                    )
                while self._admin_q:
                    self._admin_q.popleft()._reject("shutdown")
            self._walk_cv.notify_all()
            self._upd_cv.notify_all()
        for t in self._threads:
            t.join(timeout)
        self._threads.clear()
        return self.stats()

    def __enter__(self) -> "WalkServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            out = dict(self._stats)
            out["queue_depth"] = len(self._walk_q)
            out["update_depth"] = len(self._upd_q)
            out["generation"] = (
                self._generation.gen_id if self._generation else -1
            )
            gen = self._generation
            out["coverage"] = (
                float(getattr(gen.image, "coverage", 1.0)) if gen else 1.0
            )
            out["down_shards"] = tuple(
                sorted(getattr(gen.image, "down", ()) or ())
            ) if gen else ()
        return out

    @property
    def generation(self) -> Optional[Generation]:
        return self._generation

    def assert_no_lost(self) -> dict:
        """The zero-lost ledger: submitted == served+shed+rejected+failed.

        Call after ``stop()``; raises AssertionError when any admitted
        request neither resolved nor remains queued (i.e. was silently
        lost).  Returns the stats dict for convenience.
        """
        s = self.stats()
        resolved = (
            s["served"] + s["shed_expired"] + s["rejected_backpressure"]
            + s["rejected_other"] + s["failed"]
        )
        assert resolved == s["submitted"] and s["queue_depth"] == 0, (
            f"lost walk requests: submitted={s['submitted']} "
            f"resolved={resolved} queued={s['queue_depth']}"
        )
        u_resolved = (
            s["updates_applied"] + s["updates_failed"] + s["updates_rejected"]
        )
        assert u_resolved == s["updates_submitted"] and s["update_depth"] == 0, (
            f"lost updates: submitted={s['updates_submitted']} "
            f"resolved={u_resolved} queued={s['update_depth']}"
        )
        return s

    # ------------------------------------------------------------------
    # admission (caller threads)
    # ------------------------------------------------------------------
    def _resolve_reject(self, ticket, reason, *, walk: bool,
                        retry_after=None):
        """Reject + account under self._lock (callers hold it)."""
        key = (
            "rejected_backpressure" if reason == "backpressure"
            else "shed_expired" if reason == "expired"
            else "rejected_other"
        )
        if walk:
            self._stats[key] += 1
        else:
            self._stats["updates_rejected"] += 1
        return ticket._reject(reason, retry_after)

    def submit_walk(
        self,
        seeds=None,
        *,
        weights=None,
        visits0=None,
        steps: int = 4,
        timeout: Optional[float] = None,
    ) -> WalkTicket:
        """Admit one walk request; returns a ticket (maybe pre-rejected).

        ``seeds`` (vertex ids, optionally with per-seed ``weights``) or a
        full ``visits0`` row [nv] define the initial visit vector; the
        dispatcher materializes it against the serving generation's
        vertex count.  ``timeout`` seconds (default: the server's
        ``default_timeout``) bound end-to-end latency — an expired
        request is shed, never walked.  Rejections resolve the ticket
        immediately with ``reason`` and, for backpressure, a
        ``retry_after`` hint; they are never raised here (``result()``
        raises :class:`RejectedError` for the caller that wants one).
        """
        timeout = self.default_timeout if timeout is None else timeout
        now = time.monotonic()
        deadline = None if timeout is None else now + float(timeout)
        t = WalkTicket(seeds, weights, visits0, steps, deadline)
        with self._lock:
            self._stats["submitted"] += 1
            try:
                faultinject.fire("serve.enqueue")
            except Exception as e:  # injected enqueue fault: clean reject
                t.error = e
                return self._resolve_reject(t, "enqueue_fault", walk=True)
            if self._closed:
                return self._resolve_reject(t, "shutdown", walk=True)
            depth = len(self._walk_q)
            if depth >= self.max_queue:
                retry_after = (depth - self.max_queue + 1) * self._ema_service_s
                return self._resolve_reject(
                    t, "backpressure", walk=True, retry_after=retry_after
                )
            if deadline is not None and deadline <= now:
                return self._resolve_reject(t, "expired", walk=True)
            self._walk_q.append(t)
            self._walk_cv.notify()
        return t

    def submit_update(
        self,
        plan: Optional[updates.UpdatePlan] = None,
        *,
        inserts=None,
        deletes=None,
    ) -> UpdateTicket:
        """Admit one update; the ticket acks when a sealed generation
        contains it (readers can see it) — never earlier."""
        if plan is None:
            plan = updates.plan_update(inserts=inserts, deletes=deletes)
        t = UpdateTicket(plan)
        with self._lock:
            self._stats["updates_submitted"] += 1
            try:
                faultinject.fire("serve.enqueue")
            except Exception as e:
                t.error = e
                return self._resolve_reject(t, "enqueue_fault", walk=False)
            if self._closed:
                return self._resolve_reject(t, "shutdown", walk=False)
            if len(self._upd_q) >= self.update_queue_max:
                retry_after = len(self._upd_q) * self._ema_service_s
                return self._resolve_reject(
                    t, "backpressure", walk=False, retry_after=retry_after
                )
            self._upd_q.append(t)
            self._upd_cv.notify()
        return t

    def run_on_writer(self, fn, *, reseal: bool = False) -> AdminTicket:
        """Run ``fn(server)`` on the writer thread; returns an AdminTicket.

        The callable executes serialized with plan applies and seals —
        the only safe place to mutate the live representation from
        outside (quarantine a shard, reintegrate via
        ``DurableGraph.rebuild_shard``, inject chaos).  With
        ``reseal=True`` the writer seals a fresh generation right after,
        so readers observe the admin change on their next dispatch;
        leave it False for mutations that must NOT reach readers until
        an audit passes (e.g. modeled corruption).
        """
        t = AdminTicket(fn, reseal)
        with self._lock:
            if self._closed:
                return t._reject("shutdown")
            self._admin_q.append(t)
            self._upd_cv.notify()
        return t

    def request_quarantine(self, sid: int) -> None:
        """Ask the writer to quarantine shard ``sid`` (idempotent)."""
        with self._lock:
            self._pending_quarantine.add(int(sid))
            self._upd_cv.notify()

    # ------------------------------------------------------------------
    # writer thread: control → apply → audit → seal → ack
    # ------------------------------------------------------------------
    def _base_rep(self):
        """The shard-bearing representation (unwraps DurableGraph.rep)."""
        return getattr(self._rep, "rep", self._rep)

    def _note_quarantines(self) -> bool:
        """Sync ``_known_down`` with the live rep; count new quarantines.

        Returns True when the down-set changed (quarantine OR
        reintegration) — either way the serving generation is stale and
        the writer must reseal.
        """
        down = set(getattr(self._base_rep(), "down", ()) or ())
        if down == self._known_down:
            return False
        new = down - self._known_down
        self._known_down = down
        if new:
            with self._lock:
                self._stats["shard_quarantines"] += len(new)
        return True

    def _drain_control(self) -> bool:
        """Apply queued quarantine requests + admin ops (writer thread).

        Returns True when the serving generation must be resealed.
        """
        with self._lock:
            sids = sorted(self._pending_quarantine)
            self._pending_quarantine.clear()
            admin = list(self._admin_q)
            self._admin_q.clear()
        dirty = False
        base = self._base_rep()
        for sid in sids:
            if hasattr(base, "quarantine") and sid not in getattr(
                base, "down", ()
            ):
                base.quarantine(int(sid))
        if sids:
            dirty |= self._note_quarantines()
        for t in admin:
            try:
                t.value = t.fn(self)
            except Exception as e:
                t._fail(e)
            else:
                with self._lock:
                    self._stats["admin_ops"] += 1
                t._resolve(SERVED)
                dirty |= t.reseal
            dirty |= self._note_quarantines()
        return dirty

    def _audit_tick(self) -> bool:
        """One paced AuditScheduler tick; quarantines on detection.

        Returns True when a shard was quarantined (reseal needed).
        """
        base = self._base_rep()
        if not hasattr(base, "audit_shard"):
            return False
        if self._auditor is None or self._auditor.g is not base:
            from . import failover
            self._auditor = failover.AuditScheduler(base)
        hit = self._auditor.tick()
        if hit is None:
            return False
        sid, _exc = hit
        base.quarantine(int(sid))
        with self._lock:
            self._stats["audit_detections"] += 1
        self._note_quarantines()
        return True
    def _seal_locked(self, *, initial: bool = False) -> bool:
        """Seal a new generation and ack the updates it contains.

        On failure (an injected seal fault, an exhausted fallback chain
        inside the image flush) readers keep the previous generation —
        still consistent — the applied-but-unsealed updates stay queued
        for ack, and the writer retries on its next tick.
        """
        gen_id = self._gen_counter + (0 if initial else 1)
        with jax.profiler.TraceAnnotation("serve.seal", gen=gen_id):
            try:
                faultinject.fire("serve.seal")
                img = walk_image.seal_generation(self._rep, gen_id)
            except Exception:
                self._stats["seal_failures"] += 1
                return False
            self._gen_counter = gen_id
            self._generation = Generation(
                gen_id=gen_id, image=img, seq=self._seq
            )
            self._stats["seals"] += 1
            for t in self._seal_pending:
                t.generation = gen_id
                t._resolve(SERVED)
            self._seal_pending.clear()
        return True

    def _writer_idle(self) -> bool:
        """No update, seal, admin op or quarantine waits (lock held)."""
        return (
            not self._upd_q and not self._closed
            and not self._seal_pending and not self._admin_q
            and not self._pending_quarantine
        )

    def _writer_loop(self) -> None:
        audit_round = 0
        while True:
            with self._lock:
                if self._writer_idle():
                    with jax.profiler.TraceAnnotation("serve.writer.wait"):
                        while self._writer_idle():
                            self._upd_cv.wait(0.05)
                            if self.audit_every:
                                break  # idle tick: keep the audit sweep moving
                if (
                    self._closed and not self._upd_q
                    and not self._seal_pending and not self._admin_q
                ):
                    return
                group = [
                    self._upd_q.popleft()
                    for _ in range(min(len(self._upd_q), self.seal_group_max))
                ]
            taken = time.monotonic()
            for t in group:
                t.taken_at = taken
            dirty = self._drain_control()
            for t in group:
                try:
                    # rep protocol adapter: single-device reps return
                    # (rep, dm); ShardedGraph.apply mutates in place and
                    # returns None (ΔM read off the live edge count).
                    m0 = int(getattr(self._rep, "m", 0))
                    out = self._rep.apply(t.plan)
                    if out is None:
                        dm = int(getattr(self._rep, "m", m0)) - m0
                    else:
                        self._rep, dm = out
                    t.dm = int(dm)
                    t.applied_at = time.monotonic()
                    self._seq += 1
                    with self._lock:
                        self._stats["updates_applied"] += 1
                        self._seal_pending.append(t)
                except Exception as e:
                    # the plan did not take effect (validation, or an
                    # exhausted fallback chain before any state landed);
                    # the ticket fails VISIBLY and the stream continues.
                    with self._lock:
                        self._stats["updates_failed"] += 1
                    t._fail(e)
            # a sharded apply quarantines faulted shards in place
            # (non-raising, §17) — pick those up and reseal degraded
            dirty |= self._note_quarantines()
            audit_round += 1
            if self.audit_every and audit_round >= self.audit_every:
                audit_round = 0
                dirty |= self._audit_tick()
            if group or self._seal_pending or dirty:
                with self._lock:
                    if not self._seal_locked():
                        # failed seal: retry after a short pause so an
                        # injected multi-shot fault can't spin the CPU
                        pass
                if self._seal_pending:
                    time.sleep(self.retry_backoff)

    # ------------------------------------------------------------------
    # dispatcher thread: coalesce → shed → walk → fulfil
    # ------------------------------------------------------------------
    def _take_batch(self) -> Optional[list]:
        """Pop up to batch_max same-steps requests (head-of-line steps)."""
        with self._lock:
            if not self._walk_q and not self._closed:
                with jax.profiler.TraceAnnotation("serve.dispatch.wait"):
                    while not self._walk_q and not self._closed:
                        self._walk_cv.wait(0.05)
            if not self._walk_q:
                return None
            head = self._walk_q.popleft()
            batch = [head]
            kept = collections.deque()
            while self._walk_q and len(batch) < self.batch_max:
                t = self._walk_q.popleft()
                if t.steps == head.steps:
                    batch.append(t)
                else:
                    kept.append(t)
            kept.extend(self._walk_q)
            self._walk_q = kept
        taken = time.monotonic()
        for t in batch:
            t.taken_at = taken
        return batch

    def _visits_row(self, t: WalkTicket, nv: int) -> Optional[np.ndarray]:
        """Materialize the request's initial visit vector, or reject."""
        if t.visits_row is not None:
            row = np.asarray(t.visits_row, np.float32).reshape(-1)
            if row.shape[0] != nv:
                with self._lock:
                    self._resolve_reject(t, "shape_mismatch", walk=True)
                return None
            return row
        seeds = np.atleast_1d(np.asarray(t.seeds, np.int64))
        if seeds.size == 0 or seeds.min() < 0 or seeds.max() >= nv:
            with self._lock:
                self._resolve_reject(t, "seed_out_of_range", walk=True)
            return None
        row = np.zeros(nv, np.float32)
        w = (
            np.ones(seeds.shape[0], np.float32)
            if t.weights is None
            else np.asarray(t.weights, np.float32).reshape(-1)
        )
        np.add.at(row, seeds, w)
        return row

    def _batch_rows(self, batch: list):
        """Shed the expired, build the survivors' rows against the serving
        generation: ``(gen, tickets, v0)`` with ``v0`` the ``[B_pad, V]``
        stack, B_pad a power of two >= 4 (the warm jit shapes)."""
        now = time.monotonic()
        live: list[WalkTicket] = []
        for t in batch:
            if t.deadline is not None and t.deadline <= now:
                with self._lock:
                    self._resolve_reject(t, "expired", walk=True)
            else:
                live.append(t)
        gen = self._generation
        nv = int(gen.image.nv)
        rows, tickets = [], []
        for t in live:
            row = self._visits_row(t, nv)
            if row is not None:
                rows.append(row)
                tickets.append(t)
        if not tickets:
            return gen, tickets, None
        b_pad = max(alloc.next_pow2(len(tickets)), 4)
        v0 = np.zeros((b_pad, nv), np.float32)
        v0[: len(tickets)] = np.stack(rows)
        return gen, tickets, v0

    def _dispatch_loop(self) -> None:
        primary = self.walk_backend
        if primary == "auto":
            primary = "pallas" if jax.default_backend() == "tpu" else "xla"
        batch_id = 0
        while True:
            batch = self._take_batch()
            if batch is None:
                return
            batch_id += 1
            try:
                with jax.profiler.TraceAnnotation(
                    "serve.walk.prepare", batch=batch_id
                ):
                    gen, tickets, v0 = self._batch_rows(batch)
                    if not tickets:
                        continue
                    t0 = time.monotonic()
                    faultinject.fire("serve.dispatch")
                    steps = int(tickets[0].steps)
                    res = gen.image.walk(
                        steps, backend=self.walk_backend, visits0=v0
                    )
                    sparse = _sw_ops.take_sparse_steps()
                meta = {"batch": batch_id, "gen": gen.gen_id,
                        "walks": len(tickets)}
                with jax.profiler.TraceAnnotation("serve.walk.device", **meta):
                    jax.block_until_ready(res)
                with jax.profiler.TraceAnnotation("serve.walk.fetch", **meta):
                    out, sparse = jax.device_get((res, sparse))
                    del res  # free the device rows before the next batch
                    self._fulfil(gen, tickets, out, time.monotonic() - t0,
                                 primary, steps, sparse)
            except Exception as e:
                # a shard-attributed walk fault (§17): ask the writer to
                # quarantine that shard, then retry the batch — against
                # the previous (still clean) generation first, and the
                # degraded reseal once the writer flips it.
                sid = getattr(e, "sid", None)
                if sid is not None:
                    self.request_quarantine(int(sid))
                self._retry_or_fail([t for t in batch if not t.done], e)

    def _fulfil(self, gen: Generation, tickets: list, out: np.ndarray,
                dt: float, primary: str, steps: int,
                sparse: Optional[int]) -> None:
        """Account a served batch and resolve its tickets.  ``sparse`` is
        the steps the walk program ran in the sparse frontier form (None
        where no such program ran: every step was dense)."""
        sparse = 0 if sparse is None else int(sparse)
        b = len(tickets)
        cov = float(getattr(gen.image, "coverage", 1.0))
        downs = tuple(sorted(getattr(gen.image, "down", ()) or ()))
        used = _fb.LAST_USED.get("slot_walk")
        with self._lock:
            if used is not None and used != primary:
                self._stats["breaker_fallbacks"] += 1
            self._stats["batches"] += 1
            self._stats["max_batch"] = max(self._stats["max_batch"], b)
            self._stats["served"] += b
            self._stats["walk_steps_sparse"] += sparse
            self._stats["walk_steps_dense"] += steps - sparse
            if cov < 1.0:
                self._stats["served_degraded"] += b
            self._ema_service_s += 0.2 * (dt / b - self._ema_service_s)
        for i, t in enumerate(tickets):
            t.visits = out[i]
            t.generation = gen.gen_id
            t.coverage = cov
            t.down_shards = downs
            t._resolve(SERVED)

    def _retry_sleep_s(self, attempt: int) -> float:
        """Jittered exponential backoff: base·2^(attempt-1), capped, with
        uniform ±50% jitter so retry storms decorrelate."""
        base = min(
            self.retry_backoff * (2.0 ** max(int(attempt) - 1, 0)),
            self.retry_max_backoff,
        )
        return base * float(self._rng.uniform(0.5, 1.5))

    def _retry_or_fail(self, tickets: list, err: Exception) -> None:
        """Bounded retry with backoff; exhausted tickets fail visibly."""
        retry, dead = [], []
        for t in tickets:
            t.attempts += 1
            (retry if t.attempts <= self.dispatch_retries else dead).append(t)
        with self._lock:
            if retry:
                self._stats["dispatch_retries"] += 1
                self._walk_q.extendleft(reversed(retry))
                self._walk_cv.notify()
            for t in dead:
                self._stats["failed"] += 1
                t._fail(err)
        if retry:
            time.sleep(self._retry_sleep_s(max(t.attempts for t in retry)))
