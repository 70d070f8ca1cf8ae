"""WalkImage — the universal traversal-image layer (DESIGN.md §11/§12).

Every representation lowers to ONE canonical device traversal image: a
packed edge buffer (``dst``/``wgt``/``rows``, SENTINEL on dead slots)
plus per-vertex ``[lo, hi)`` block intervals — exactly the operand set
the fused ``kernels/slot_walk`` engine consumes (§6).  The image is
**incrementally maintained** under update streams instead of being
re-materialized per walk:

  * representations *queue* each applied ``UpdatePlan`` on their cached
    image (``queue``), and the next walk *flushes* the queue by patching
    touched rows in place through the fused ``kernels/slot_update``
    engine — ALL pow-2 width groups of a plan in ONE dispatch
    (``fused_apply``), and, on the walk path, the k-step walk scan fused
    into the SAME program (``walk_flush``): a steady-state update/walk
    stream round is one device dispatch, zero intermediate
    materialization (§12);
  * rows are laid out in CP2AA slack-padded blocks (``alloc.edge_
    capacities``) — or DENSELY when the source layout's slack would
    dominate the walked prefix (``DENSE_THRESHOLD``, §12): ChunkedGraph
    PAGE tails and low-occupancy arenas compact to live edges only, so
    walks never drag dead lanes through the step loop;
  * a row that outgrows its slack relocates to a fresh block at the
    image's bump pointer inside the same fused dispatch;
  * the patch path falls back to a full rebuild (returning ``False`` so
    the owner drops its cache) only when the bump slack is exhausted,
    the vertex set grows, or the queue got too deep to be worth
    replaying (``MAX_PENDING``).

``DiGraph`` is the degenerate case: its arena *is* the image, so
``shared=True`` wraps the live buffers zero-copy and the rep's own
update engine keeps them current (shared images never patch).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import alloc, util

SENTINEL = util.SENTINEL

#: Queue depth beyond which replaying patches is judged worse than one
#: rebuild (each pending plan costs a fused dispatch).
MAX_PENDING = 32
#: Fraction of the BUILD-TIME occupancy below which a flush demands a
#: rebuild instead of further patching — the image-level analogue of
#: DiGraph's traversal-time compaction (§7): dead slots from relocated /
#: deleted rows otherwise accumulate in the walked prefix forever.  The
#: trigger is relative to the layout's own slack, so it fires only when
#: a rebuild would actually densify.
COMPACT_THRESHOLD = 0.5
#: Don't bother occupancy-rebuilding images smaller than this.
COMPACT_MIN_SLOTS = 4 * 128
#: Build-time live fraction below which an image build strips the source
#: layout's slack entirely (caps == degrees, occupancy 1.0) instead of
#: inheriting it — dense image compaction (§12).  CP2AA arenas build at
#: ~0.65-0.7 and keep their slack (in-place patches stay cheap);
#: ChunkedGraph's PAGE quantization builds at ~0.3 and compacts, since
#: 3x dead lanes per step cost far more than relocating grown rows.
DENSE_THRESHOLD = 0.55

#: Module-level maintenance counters; tests and benchmarks read these to
#: prove walks do zero host image work (builds) between updates, and
#: that a steady-state flush→walk round is ONE device dispatch.
STATS = {"builds": 0, "patches": 0, "rebuilds": 0, "dispatches": 0, "seals": 0}


def stats_snapshot() -> dict:
    return dict(STATS)


def seal_generation(rep, generation: int = 0) -> "WalkImage":
    """Seal ``rep``'s current state as an immutable walk generation (§16).

    The single writer calls this after applying a group of UpdatePlans;
    the returned frozen :class:`WalkImage` is what concurrent readers
    walk until the next seal — they can never observe a half-applied
    plan, because generations are immutable and the live structure's
    subsequent patches copy-on-write instead of donating shared buffers.

    Two shapes, one contract:

    * queueing reps (coo/lazy/chunked/vector2d): ``to_walk_image()``
      flushes or rebuilds the cached image, then :meth:`WalkImage.seal`
      snapshots it O(1) and arms the COW flag on the live image;
    * arena-backed reps (DiGraph, ``shared=True`` images): the rep's own
      per-buffer COW *is* the isolation — ``rep.snapshot()`` seals the
      arena buffers (the next in-place update detaches only what it
      writes, §10) and the snapshot's image wrap becomes the frozen
      generation.  The snapshot handle is dropped; the image keeps its
      host geometry arrays alive.

    Reps with their own ``seal_generation`` (``ShardedGraph``: per-shard
    seals + quarantine masking, §17) delegate wholesale.
    """
    own = getattr(rep, "seal_generation", None)
    if own is not None:
        return own(generation)
    img = rep.to_walk_image()
    if not img.shared:
        return img.seal(generation)
    snap = rep.snapshot()
    gen = snap.to_walk_image()
    gen.generation = int(generation)
    gen._frozen = True
    # detach from the snapshot handle: the generation must stay exactly
    # as sealed even if someone mutates the snapshot rep later.
    snap._image = None
    STATS["seals"] += 1
    return gen


def reverse_walk_via_image(rep, steps: int, *, visits0=None):
    """The shared reverse_walk body of every image-queueing representation.

    Try the fused flush→walk dispatch on the cached image (§12); fall
    back to the eager flush-or-rebuild path (``to_walk_image``) when the
    image is absent or can only be rebuilt.
    """
    img = rep._image
    if img is not None:
        out = img.walk_flush(steps, visits0=visits0)
        if out is not None:
            return out
    return rep.to_walk_image().walk(steps, visits0=visits0)


@dataclasses.dataclass
class WalkImage:
    """Packed traversal image + host block geometry (one per owner rep)."""

    # device payload
    dst: jnp.ndarray   # int32 [cap_e], SENTINEL on dead slots
    wgt: jnp.ndarray   # f32   [cap_e] (carried for the patch merges)
    rows: jnp.ndarray  # int32 [cap_e] slot owner (stale allowed on dead)
    # host block geometry (CP2AA classes, or exact degrees when dense)
    starts: np.ndarray  # int64 [>= nv], -1 = no block
    caps: np.ndarray    # int64 [>= nv]
    degs: np.ndarray    # int64 [>= nv]
    nv: int             # vertices the walk covers (visits length)
    bump: int           # first never-allocated slot
    live: int           # live edges in the image
    #: True when dst/wgt/rows alias the owner's own arena (DiGraph):
    #: zero-cost wrap, kept current by the rep — never patched here.
    shared: bool = False
    #: occupancy as built — the densest this layout can be; the compact
    #: trigger fires relative to it (see COMPACT_THRESHOLD).
    base_occupancy: float = 1.0
    # device [lo, hi) interval cache + queued plans
    _blocks: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False
    )
    _pending: list = dataclasses.field(
        default_factory=list, repr=False, compare=False
    )
    #: set once the queue overflowed MAX_PENDING (or a fused walk left
    #: the occupancy below the compaction trigger): the image can only
    #: be rebuilt, so further plans are dropped instead of pinned.
    _stale: bool = dataclasses.field(default=False, repr=False, compare=False)
    #: sealed-generation id (§16); -1 on live (unsealed) images.
    generation: int = -1
    #: True on a sealed generation: the image is read-only — ``queue``
    #: raises and the patch engine never touches it.  Readers walk it
    #: while the live writer image keeps patching (snapshot isolation).
    _frozen: bool = dataclasses.field(default=False, repr=False, compare=False)
    #: True while a sealed generation still shares this live image's
    #: device payload: the NEXT patch must not donate dst/wgt/rows (the
    #: per-buffer COW — jax immutability makes the non-donated merge a
    #: copy-on-write detach; the patch outputs are fresh buffers, so the
    #: flag clears after one dispatch).
    _cow: bool = dataclasses.field(default=False, repr=False, compare=False)

    # ------------------------------------------------------------------
    @property
    def cap_e(self) -> int:
        return int(self.dst.shape[0])

    @property
    def occupancy(self) -> float:
        """Live-edge fraction of the image's allocated slot prefix."""
        return self.live / max(int(self.bump), 1)

    # ------------------------------------------------------------------
    # integrity (DESIGN.md §13 — the auditor's image half)
    # ------------------------------------------------------------------
    def audit(self) -> dict:
        """Geometry + content invariant sweep; raises ``AuditError``.

        Asserts everything the patch engine and the walk scan rely on:
        blocks live inside the bump frontier and are pairwise disjoint,
        every live slot carries an in-range destination owned by its
        block's row and rows stay strictly ascending, slack slots are
        SENTINEL (the merge gather masks on it — a non-SENTINEL slack
        slot would resurrect a ghost edge on the next patch), and the
        per-row degrees account for exactly ``self.live`` edges.
        """
        from ..runtime import faultinject as _fi

        chk = _fi._check
        nv, bump, cap_e = int(self.nv), int(self.bump), self.cap_e
        chk(0 <= bump <= cap_e, f"bump {bump} outside [0, cap_e {cap_e}]")
        chk(
            self.starts.shape[0] >= nv
            and self.caps.shape[0] >= nv
            and self.degs.shape[0] >= nv,
            "block geometry arrays shorter than nv",
        )
        starts = np.asarray(self.starts[:nv], np.int64)
        caps = np.asarray(self.caps[:nv], np.int64)
        degs = np.asarray(self.degs[:nv], np.int64)
        chk(bool((degs >= 0).all()), "negative image degree")
        chk(bool((caps >= degs).all()), "image degree exceeds block capacity")
        blocked = caps > 0
        chk(bool((degs[~blocked] == 0).all()), "edges on a block-less row")
        chk(bool((starts[blocked] >= 0).all()), "blocked row with start < 0")
        chk(
            bool(((starts[blocked] + caps[blocked]) <= bump).all()),
            "block extends past the bump frontier",
        )
        if blocked.any():
            order = np.argsort(starts[blocked], kind="stable")
            s_b, c_b = starts[blocked][order], caps[blocked][order]
            chk(
                bool(((s_b[:-1] + c_b[:-1]) <= s_b[1:]).all()),
                "overlapping blocks",
            )
        m = int(degs.sum())
        chk(m == int(self.live), f"degree sum {m} != image live {int(self.live)}")
        n_blocks = int(blocked.sum())
        if m:
            d = np.asarray(self.dst)
            w = np.asarray(self.wgt)
            r = np.asarray(self.rows)
            first = np.cumsum(degs) - degs
            gidx = np.repeat(starts, degs) + (
                np.arange(m, dtype=np.int64) - np.repeat(first, degs)
            )
            owner = np.repeat(np.arange(nv, dtype=np.int64), degs)
            dl, wl, rl = d[gidx], w[gidx], r[gidx]
            chk(not bool((dl == SENTINEL).any()), "SENTINEL inside a live prefix")
            chk(
                bool((dl >= 0).all()) and bool((dl < nv).all()),
                "image dst id out of [0, nv)",
            )
            chk(bool((rl == owner).all()), "live slot owned by the wrong row")
            chk(bool(np.isfinite(wl).all()), "non-finite live image weight")
            interior = owner[1:] == owner[:-1]
            chk(
                not bool((interior & (dl[1:] <= dl[:-1])).any()),
                "image row not strictly ascending",
            )
        slack = caps - degs
        if int(slack.sum()):
            sfirst = np.cumsum(slack) - slack
            sidx = np.repeat(starts + degs, slack) + (
                np.arange(int(slack.sum()), dtype=np.int64)
                - np.repeat(sfirst, slack)
            )
            chk(
                bool((np.asarray(self.dst)[sidx] == SENTINEL).all()),
                "non-SENTINEL slack slot",
            )
        return {
            "blocks": n_blocks,
            "bump": bump,
            "slack": int(slack.sum()),
            "occupancy": self.occupancy,
        }

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def from_csr_arrays(cls, offsets, dst, wgt, nv: int, *,
                        dense: Optional[bool] = None,
                        min_cap_e: int = 0) -> "WalkImage":
        """Build a slack-padded OR dense image from CSR-ordered arrays.

        Reuses the ingest engine's ``arena_image`` fill (DESIGN.md §10):
        CP2AA block placement and a numpy fill on host, one transfer for
        the device payload.  ``dense=None`` applies the §12 compaction
        policy: when the CP2AA layout's live fraction would fall below
        ``DENSE_THRESHOLD``, blocks take their exact degree (occupancy
        1.0) so the walk processes live edges only.  ``cap_e`` keeps
        >= 25% bump headroom either way so grown rows can relocate
        without an immediate rebuild.  ``min_cap_e`` floors the slot
        capacity — the sharded layer (§14) passes one common floor so
        every shard's image compiles to the same program shape.
        """
        from ..kernels.csr_build import ops as _cb_ops

        o = np.asarray(offsets, np.int64)
        nv = int(nv)
        deg = np.diff(o)
        m = int(o[-1]) if o.shape[0] else 0
        caps = np.where(deg > 0, alloc.edge_capacities(deg), 0)
        total = int(caps.sum())
        if dense is None:
            dense = m > 0 and m < DENSE_THRESHOLD * total
        if dense:
            caps = deg.copy()
            total = m
        csum = np.cumsum(caps)
        starts = np.where(caps > 0, csum - caps, -1)
        cap_e = alloc.pow2_with_headroom(total, 1.0 if dense else 0.25)
        cap_e = max(cap_e, int(min_cap_e))
        w = wgt if wgt is not None else np.ones(m, np.float32)
        # slice padded source buffers to the live prefix: the SENTINEL
        # tail capacity would be copied to the host for nothing
        dst_d, wgt_d, rows_d = _cb_ops.arena_image(
            o, dst[:m], w[:m], starts, caps, cap_e, nv,
        )
        STATS["builds"] += 1
        return cls(
            dst=dst_d, wgt=wgt_d, rows=rows_d,
            starts=starts.astype(np.int64), caps=caps.astype(np.int64),
            degs=deg.astype(np.int64), nv=nv, bump=total, live=m,
            base_occupancy=m / max(total, 1),
        )

    @classmethod
    def from_blocks(cls, dst, wgt, rows, starts, caps, degs, nv: int,
                    bump: int, live: int, *, shared: bool = False) -> "WalkImage":
        """Wrap pre-blocked device buffers (DiGraph arena, page gathers)."""
        STATS["builds"] += 1
        return cls(
            dst=dst, wgt=wgt, rows=rows,
            starts=np.asarray(starts, np.int64),
            caps=np.asarray(caps, np.int64),
            degs=np.asarray(degs, np.int64),
            nv=int(nv), bump=int(bump), live=int(live), shared=shared,
            base_occupancy=int(live) / max(int(bump), 1),
        )

    # ------------------------------------------------------------------
    # generation sealing (DESIGN.md §16 — snapshot-isolated serving)
    # ------------------------------------------------------------------
    def seal(self, generation: int = 0) -> "WalkImage":
        """Seal the current state as an immutable read-only generation.

        O(1) on device: the sealed image *shares* the live device payload
        (jax arrays are immutable) and copies only the small host
        geometry arrays.  The live image is flagged ``_cow`` so its next
        patch suppresses buffer donation — the merge then writes fresh
        buffers instead of invalidating the generation's (per-buffer
        COW, §10), after which the flag clears and donation resumes.
        Readers walk the sealed generation while the writer patches the
        live image: a reader can never observe a half-applied plan.

        Requires a flushed image (no queued plans, not stale) — the
        serve layer seals via :func:`seal_generation`, which flushes or
        rebuilds first.  Shared (arena-backed) images cannot seal here:
        their owner's update engine mutates host metadata in place, so
        the owner rep must be snapshotted instead (``seal_generation``
        handles that too).
        """
        if self.shared:
            raise ValueError("seal(): shared image — snapshot the owner rep")
        if self._pending or self._stale:
            raise ValueError("seal(): image has unflushed plans")
        gen = WalkImage(
            dst=self.dst, wgt=self.wgt, rows=self.rows,
            starts=self.starts[: self.nv].copy(),
            caps=self.caps[: self.nv].copy(),
            degs=self.degs[: self.nv].copy(),
            nv=self.nv, bump=self.bump, live=self.live,
            base_occupancy=self.base_occupancy,
            generation=int(generation), _frozen=True,
        )
        self._cow = True
        STATS["seals"] += 1
        return gen

    # ------------------------------------------------------------------
    # incremental maintenance
    # ------------------------------------------------------------------
    def queue(self, plan) -> None:
        """Record an applied UpdatePlan; the next walk flushes it.

        Past MAX_PENDING the image is only ever rebuilt, so the queue is
        dropped and the image marked stale — an update-only stream must
        not pin every plan's batch arrays in memory until someone walks.
        """
        if self._frozen:
            raise RuntimeError(
                f"sealed walk generation {self.generation} is read-only"
            )
        if self.shared or self._stale:  # shared: the arena IS the image
            return
        self._pending.append(plan)
        if len(self._pending) > MAX_PENDING:
            self._pending.clear()
            self._stale = True

    def block_ranges(self, rows: np.ndarray) -> np.ndarray:
        """``[K, 2]`` half-open slot ranges of ``rows``'s CURRENT blocks.

        The §15 differential checkpointer calls this before AND after a
        patch: a relocated row's old slots are cleared to SENTINEL (the
        walk masks on ``dst == SENTINEL`` over the whole bump prefix), so
        both the vacated and the new extent are dirty bytes.  Rows
        without a block contribute nothing.
        """
        rows = np.asarray(rows, np.int64)
        st = np.asarray(self.starts[rows], np.int64)
        cp = np.asarray(self.caps[rows], np.int64)
        has = (st >= 0) & (cp > 0)
        return np.stack([st[has], st[has] + cp[has]], axis=1)

    def _needs_compact(self) -> bool:
        return (
            self.bump >= COMPACT_MIN_SLOTS
            and self.occupancy < COMPACT_THRESHOLD * self.base_occupancy
        )

    def flush(self) -> bool:
        """Patch all queued plans in; False = owner must rebuild."""
        if self._stale:
            STATS["rebuilds"] += 1
            return False
        if not self._pending:
            return True
        while self._pending:
            if not self._patch_one(self._pending[0]):
                STATS["rebuilds"] += 1
                return False
            self._pending.pop(0)
        # occupancy-triggered compaction (§7, image-level): once dead
        # slots dominate the walked prefix — relative to how dense this
        # layout was as built — one rebuild beats every subsequent walk
        # dragging them through the step loop.
        if self._needs_compact():
            STATS["rebuilds"] += 1
            return False
        return True

    # -- patch pipeline: host planning, fused dispatch, host commit ------
    def _plan_patch(self, plan):
        """Host half of one plan's patch: geometry + dispatch operands.

        Mirrors ``DiGraph._apply_impl``'s planning against the image's
        own geometry, producing the operand set of ONE fused
        ``slot_update.fused_apply`` dispatch (every pow-2 width class of
        the plan merges in the same program; grown rows land in fresh
        bump blocks).  Returns None when only a rebuild can represent
        the result (new vertices, or a grown row with no bump slack
        left) — all failure checks precede any state mutation, so a
        failed planning pass is side-effect free.
        """
        from ..kernels.slot_update import ops as _su_ops

        if plan.n_ops == 0:
            return ()
        if plan.max_insert_vertex() >= self.nv:
            return None  # vertex growth changes the visits shape: rebuild
        sel, rows, deg_old, ins_count = plan.active_rows(self.degs, self.nv)
        if sel.shape[0] == 0:
            return ()
        old_caps = self.caps[rows]
        old_starts = self.starts[rows]
        ub = deg_old + ins_count
        grow = ub > old_caps
        new_caps = old_caps.copy()
        new_starts = old_starts.copy()
        if grow.any():
            need = alloc.edge_capacities(ub[grow])
            if self.bump + int(need.sum()) > self.cap_e:
                return None  # slack exhausted: rebuild repacks densely
            g_idx = np.nonzero(grow)[0]
            new_caps[g_idx] = need
            new_starts[g_idx] = self.bump + (np.cumsum(need) - need)
            self.bump += int(need.sum())

        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
        has_moves = bool(grow.any())
        touched = int(new_caps.sum() + old_caps[grow].sum())
        scatter = _su_ops.choose_scatter(self.cap_e, touched)
        groups, layout = plan.fused_groups(
            sel, rows, deg_old, grow,
            old_starts, old_caps, new_starts, new_caps,
            _su_ops.width_floor(), self.nv,
        )
        slot_map = owner_patch = None
        rebuild_hi = 0
        if not scatter:
            rebuild_hi = self.edges_hi()  # post-growth bump, same lattice
            slot_map, owner_patch = _su_ops.host_patch_layout(
                layout, rows, old_starts, old_caps, new_starts, new_caps,
                grow, rebuild_hi, self.nv, has_moves,
            )
        return dict(
            rows=rows, deg_old=deg_old, grow=grow,
            new_caps=new_caps, new_starts=new_starts,
            groups=groups, layout=layout, backend=backend,
            scatter=scatter, slot_map=slot_map, owner_patch=owner_patch,
            rebuild_hi=rebuild_hi,
        )

    def _commit_patch(self, prep, counts_list) -> None:
        """Install the post-dispatch geometry (degrees, moved blocks)."""
        rows, deg_old = prep["rows"], prep["deg_old"]
        net = 0
        for (_wv, gsel, _a), counts in zip(prep["layout"], counts_list):
            counts = np.asarray(counts, dtype=np.int64)[: gsel.shape[0]]
            self.degs[rows[gsel]] = counts
            net += int(counts.sum() - deg_old[gsel].sum())
        if prep["grow"].any():
            self.starts[rows] = prep["new_starts"]
            self.caps[rows] = prep["new_caps"]
        self.live += net
        self._blocks = None
        STATS["patches"] += 1

    def _patch_one(self, plan) -> bool:
        """Apply one plan to the image: ONE fused dispatch, all groups."""
        from ..kernels.slot_update import ops as _su_ops

        prep = self._plan_patch(plan)
        if prep is None:
            return False
        if prep == ():
            return True
        self.dst, self.wgt, self.rows, counts, _ = _su_ops.fused_apply(
            self.dst, self.wgt, self.rows, prep["groups"],
            scatter=prep["scatter"], backend=prep["backend"],
            donate=not self._cow,
            slot_map=prep["slot_map"], owner_patch=prep["owner_patch"],
            rebuild_hi=prep["rebuild_hi"],
        )
        self._cow = False  # outputs are fresh buffers; generations detached
        STATS["dispatches"] += 1
        self._commit_patch(prep, counts)
        return True

    # ------------------------------------------------------------------
    # walking
    # ------------------------------------------------------------------
    def edges_hi(self) -> int:
        """Bump prefix bound, quantized so jit shapes stay coarse (§6).

        cap_e/8 granularity (<= 8 shapes per capacity): under update
        streams the bump pointer only grows, and every quantum crossing
        recompiles the walk scan — a coarse lattice trades <= 12.5% dead
        pad slots for rounds of warm-shape walks between crossings.
        """
        q = max(self.cap_e // 8, 128)
        return min(-(-max(int(self.bump), 1) // q) * q, self.cap_e)

    def device_blocks(self):
        """Device [lo, hi) interval arrays, memoized until the next patch."""
        if self._blocks is None:
            starts = self.starts[: self.nv]
            has_block = starts >= 0
            lo = np.where(has_block, starts, 0).astype(np.int32)
            hi = np.where(
                has_block, starts + self.degs[: self.nv], 0
            ).astype(np.int32)
            self._blocks = (jnp.asarray(lo), jnp.asarray(hi))
        return self._blocks

    def walk(
        self,
        steps: int,
        *,
        backend: str = "auto",
        normalize: bool = False,
        interpret: bool = False,
        visits0: Optional[jnp.ndarray] = None,
    ) -> jnp.ndarray:
        """k-step reverse walk over the image via the slot_walk engine.

        ``visits0`` may be a ``[B, num_vertices]`` stack of initial visit
        vectors — all B walks then ride the same fused step programs.
        Assumes the image is flushed (owners call ``walk_flush`` or
        ``to_walk_image()`` first).
        """
        from ..kernels.slot_walk import ops as _sw_ops

        STATS["dispatches"] += 1
        return _sw_ops.slot_walk_image(
            self,
            steps,
            backend=backend,
            normalize=normalize,
            interpret=interpret,
            visits0=visits0,
        )

    def walk_flush(
        self,
        steps: int,
        *,
        backend: str = "auto",
        normalize: bool = False,
        interpret: bool = False,
        visits0: Optional[jnp.ndarray] = None,
    ) -> Optional[jnp.ndarray]:
        """Flush queued plans AND walk — fused into ONE dispatch (§12).

        The steady-state stream round (one queued plan, then a walk)
        lowers to a single jitted program: the plan's merge groups run
        as a prologue, the [lo, hi) geometry updates in-program from the
        merge counts, and the step scan consumes the patched buffers
        directly — no intermediate flush dispatch, no host round-trip
        before the walk.  Deeper queues flush all but the last plan
        first (one fused dispatch each).  Returns None when the image
        can only be rebuilt — the owner falls back to
        ``to_walk_image().walk(...)`` (rebuild accounting happens there,
        in ``flush``; a failed planning pass here is side-effect free).
        """
        from ..kernels.slot_update import ops as _su_ops

        if self.shared or self._stale:
            return None if self._stale else self.walk(
                steps, backend=backend, normalize=normalize,
                interpret=interpret, visits0=visits0,
            )
        while len(self._pending) > 1:
            if not self._patch_one(self._pending[0]):
                return None
            self._pending.pop(0)
        if not self._pending:
            return self.walk(
                steps, backend=backend, normalize=normalize,
                interpret=interpret, visits0=visits0,
            )
        prep = self._plan_patch(self._pending[0])
        if prep is None:
            return None
        if prep == ():
            self._pending.pop(0)
            return self.walk(
                steps, backend=backend, normalize=normalize,
                interpret=interpret, visits0=visits0,
            )
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "xla"
        nwalks = 0 if visits0 is None else int(visits0.shape[0])
        if nwalks:
            visits0 = jnp.asarray(visits0, jnp.float32)
        lo, hi = self.device_blocks()
        self.dst, self.wgt, self.rows, counts, walk_out = _su_ops.fused_apply(
            self.dst, self.wgt, self.rows, prep["groups"],
            scatter=prep["scatter"], backend=prep["backend"],
            donate=not self._cow,
            slot_map=prep["slot_map"], owner_patch=prep["owner_patch"],
            rebuild_hi=prep["rebuild_hi"],
            walk=(steps, self.nv, self.edges_hi(), nwalks,
                  bool(normalize), backend),
            lo=lo, hi=hi, visits0=visits0,
            interpret=interpret,
        )
        self._cow = False  # outputs are fresh buffers; generations detached
        STATS["dispatches"] += 1
        self._pending.pop(0)
        self._commit_patch(prep, counts)
        visits, lo2, hi2 = walk_out
        self._blocks = (lo2, hi2)  # in-program-updated geometry, reusable
        if self._needs_compact():
            # this walk already ran on the sparse image; make the NEXT
            # access rebuild densely instead of patching further.
            self._stale = True
        return visits
