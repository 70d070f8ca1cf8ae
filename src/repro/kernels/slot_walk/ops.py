"""jit'd wrapper: prefix-tile the slotted buffer, scan the step loop.

Two jitted backends behind one dispatcher:

  * ``pallas``  — the MXU tile kernels (kernel.py); ``interpret=True`` runs
    the same programs on CPU for parity tests.
  * ``xla``     — identical prefix/tile semantics via plain jnp ops
    (the fast path off-TPU, and the shape the Pallas kernels must match).

When the caller supplies per-vertex [lo, hi) block intervals (every
``WalkImage`` does), BOTH backends use the scatter-free hierarchical
prefix formulation (``make_blocked_step``): the per-slot ``slot_rows``
operand is folded into the interval geometry and each step moves only
the gather plane plus O(V) interval reads — roughly half the bytes of
the segment-sum formulation.  The legacy rows-carrying paths remain for
interval-less callers (raw arenas, the seed baseline).

A batched walk whose seeds reach few vertices takes the sparse frontier
step instead (``make_frontier_step``): it reads the gather plane once per
step to find the slots its frontier hits, then only those slots and
their owners in ``slot_rows``.  The batched program picks the form from
the frontier it observes and falls back to the dense scan, in the same
program, when the frontier outgrows the static widths.

Both only process ``edges_hi`` slots (the arena's bump prefix, rounded up
to a power of two by the caller so the jit cache stays O(log CAP_E))
instead of the full CAP_E buffer — on updated graphs that alone is the
difference between walking the paper's live edges and walking every dead
SENTINEL lane the allocator ever reserved.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

from ...core import util
from .. import fallback as _fb
from . import kernel as _kernel
from . import ref as _ref

SENTINEL = util.SENTINEL
EB = 128  # slots per tile (MXU-native)

#: per thread: the sparse step count of the last batched blocked walk
_last = threading.local()


def _tiles(e: int) -> int:
    """Tiles covering ``e`` slots, rounded up to a multiple of 8 so the
    tile planes (and their [B*T, EB] stacks) split into whole Pallas
    blocks without a padding copy inside the step loop."""
    return -(-max(-(-e // EB), 1) // 8) * 8


def _prep(dst, slot_rows, num_vertices: int, edges_hi: int):
    """Slice the live prefix, mask dead slots, pad to whole tiles.

    Dead/pad slots get row ``sink`` and gather index ``num_vertices`` —
    the step loop extends ``visits`` with a zero sink entry, so no
    per-step masking is needed (masks are folded once, here, outside the
    scan).
    """
    e = min(int(edges_hi), dst.shape[0])
    t = _tiles(e)
    e_pad = t * EB
    sink = num_vertices
    d = dst[:e]
    sr = slot_rows[:e]
    valid = (d != SENTINEL) & (sr < num_vertices)
    rows = jnp.where(valid, sr, sink).astype(jnp.int32)
    gidx = jnp.where(valid, jnp.clip(d, 0, num_vertices - 1), num_vertices)
    rows_p = jnp.full((e_pad,), sink, jnp.int32).at[:e].set(rows).reshape(t, EB)
    gidx_p = (
        jnp.full((e_pad,), num_vertices, jnp.int32).at[:e].set(gidx).reshape(t, EB)
    )
    return rows_p, gidx_p


@functools.partial(
    jax.jit,
    static_argnames=("steps", "num_vertices", "edges_hi", "normalize", "interpret"),
)
def slot_walk_pallas(
    dst: jnp.ndarray,
    slot_rows: jnp.ndarray,
    steps: int,
    num_vertices: int,
    *,
    edges_hi: int,
    normalize: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    sink = num_vertices
    rows_p, gidx_p = _prep(dst, slot_rows, num_vertices, edges_hi)
    zero = jnp.zeros((1,), jnp.float32)
    visits = jnp.ones((num_vertices,), jnp.float32)

    def body(visits, _):
        vals = jnp.concatenate([visits, zero])[gidx_p]  # sink gathers 0.0
        part, rank = _kernel.slot_walk_partials(
            rows_p, vals, sink=sink, interpret=interpret
        )
        nxt = jax.ops.segment_sum(
            part.reshape(-1),
            jnp.minimum(rank.reshape(-1), sink),
            num_segments=sink + 1,
        )[:num_vertices]
        if normalize:
            nxt = nxt / jnp.maximum(jnp.max(nxt), 1.0)
        return nxt, None

    visits, _ = jax.lax.scan(body, visits, None, length=steps)
    return visits


@functools.partial(
    jax.jit, static_argnames=("steps", "num_vertices", "edges_hi", "normalize")
)
def slot_walk_xla(
    dst: jnp.ndarray,
    slot_rows: jnp.ndarray,
    steps: int,
    num_vertices: int,
    *,
    edges_hi: int,
    normalize: bool = False,
) -> jnp.ndarray:
    sink = num_vertices
    rows_p, gidx_p = _prep(dst, slot_rows, num_vertices, edges_hi)
    rows_f = rows_p.reshape(-1)
    gidx_f = gidx_p.reshape(-1)
    zero = jnp.zeros((1,), jnp.float32)
    visits = jnp.ones((num_vertices,), jnp.float32)

    def body(visits, _):
        vals = jnp.concatenate([visits, zero])[gidx_f]  # sink gathers 0.0
        nxt = jax.ops.segment_sum(vals, rows_f, num_segments=sink + 1)[
            :num_vertices
        ]
        if normalize:
            nxt = nxt / jnp.maximum(jnp.max(nxt), 1.0)
        return nxt, None

    visits, _ = jax.lax.scan(body, visits, None, length=steps)
    return visits


def _twosum(a, b):
    """Knuth TwoSum: s + e == a + b exactly (s = fl(a+b))."""
    s = a + b
    bp = s - a
    return s, (a - (s - bp)) + (b - bp)


def _comp_combine(l, r):
    s, e = _twosum(l[0], r[0])
    return s, l[1] + r[1] + e


def _comp_scan(h, l):
    """Inclusive compensated scan of (hi, lo) pairs [B, n] along axis 1:
    returns (hi, lo) with hi+lo ≈ exact (pass ``l = 0`` for plain values).

    Blocked by EB: each EB-long block scans on its own, the block totals
    scan recursively, and every lane adds its block's exclusive base.
    One ``associative_scan`` over a long axis makes the TPU compiler's
    time grow with the axis (minutes at 10^6 tiles); EB-long rows keep
    it to seconds.
    """
    b, n = h.shape
    if n <= EB:
        return jax.lax.associative_scan(_comp_combine, (h, l), axis=1)
    m = -(-n // EB)
    pad = ((0, 0), (0, m * EB - n))
    ih, il = jax.lax.associative_scan(
        _comp_combine,
        (jnp.pad(h, pad).reshape(b, m, EB), jnp.pad(l, pad).reshape(b, m, EB)),
        axis=2,
    )
    bh, bl = _comp_scan(ih[:, :, -1], il[:, :, -1])
    zero = jnp.zeros((b, 1), h.dtype)
    eh = jnp.concatenate([zero, bh[:, :-1]], axis=1)[:, :, None]
    el = jnp.concatenate([zero, bl[:, :-1]], axis=1)[:, :, None]
    oh, ol = _comp_combine((eh, el), (ih, il))
    return oh.reshape(b, m * EB)[:, :n], ol.reshape(b, m * EB)[:, :n]


def _prep_gidx(dst, num_vertices: int, edges_hi: int):
    """Tile-padded gather indices, masked from ``dst`` ALONE.

    Dead slots carry ``dst == SENTINEL`` (arena/image invariant), so the
    interval walk needs no per-slot owner operand at all — ``slot_rows``
    is folded into the [lo, hi) block geometry and the step loop's only
    per-slot operand is this one int32 index plane (DESIGN.md §12).
    """
    e = min(int(edges_hi), dst.shape[0])
    t = _tiles(e)
    e_pad = t * EB
    d = dst[:e]
    gidx = jnp.where(
        d == SENTINEL, num_vertices, jnp.clip(d, 0, num_vertices - 1)
    ).astype(jnp.int32)
    return (
        jnp.full((e_pad,), num_vertices, jnp.int32)
        .at[:e]
        .set(gidx)
        .reshape(t, EB)
    )


def make_blocked_step(gidx_p, block_lo, block_hi, num_vertices: int, *,
                      engine: str = "xla", interpret: bool = False):
    """Build the scatter-free interval walk step (batched: [B, V] -> [B, V]).

    Each vertex's slots are one contiguous interval [block_lo, block_hi)
    (§2 invariant) and dead slots gather 0.0, so a step reduces to
    ``P[hi] - P[lo]`` over the running prefix sum of the gathered values
    — gather + prefix + a few [V] gathers, no scatter unit needed.
    Rows without a block pass lo == hi == 0.

    The prefix is *hierarchical* (DESIGN.md §12): an inclusive cumsum
    within each 128-slot tile plus a TwoSum-compensated scan over the T
    tile totals, with the difference assembled per part so the large
    bases are never rounded into the result.  ``engine`` picks the
    intra-tile level: ``xla`` (jnp.cumsum) or ``pallas`` (one triangular
    MXU matmul per tile, ``kernel.tile_cumsum``) — either way the step's
    per-slot operand set is just the gather plane, no slot_rows.

    A naive global f32 cumsum loses the row sum to cancellation once the
    total dwarfs it (err ~ ulp(total)).  The residual envelope here is
    the *intra-tile* partial, ~ulp(sum of one tile): on skewed social
    graphs a hub row sharing its tile with ~1e10-magnitude partials can
    see ~2e-4 relative error at high step counts (measured; fully
    compensating or f64-ing the intra level costs 2-10x the whole step —
    not worth it for a wall-time benchmark whose 42-step counts saturate
    f32 by design).
    """
    t = gidx_p.shape[0]
    e_pad = t * EB
    lo = jnp.clip(block_lo, 0, e_pad).astype(jnp.int32)
    hi = jnp.clip(block_hi, 0, e_pad).astype(jnp.int32)
    # split each prefix position into (tile, offset); position e_pad folds
    # onto the last tile's tail so the gather stays in range.
    q_lo = jnp.minimum(lo // EB, t - 1)
    q_hi = jnp.minimum(hi // EB, t - 1)
    r_lo = lo - q_lo * EB
    r_hi = hi - q_hi * EB
    # prefix position (q, r) reads the tile's INCLUSIVE cumsum at lane
    # r-1, or 0.0 at a tile start — no [t, EB+1] exclusive-prefix copy
    # is ever materialized in the loop
    z_lo = r_lo == 0
    z_hi = r_hi == 0
    l_lo = jnp.maximum(r_lo - 1, 0)
    l_hi = jnp.maximum(r_hi - 1, 0)

    @jax.named_scope("slot_walk.step")
    def step(visits):  # [B, num_vertices] -> [B, num_vertices]
        b = visits.shape[0]
        zrow = jnp.zeros((b, 1), jnp.float32)
        vals = jnp.concatenate([visits, zrow], axis=1)[:, gidx_p]  # [B,t,EB]
        if engine == "pallas":
            incl = _kernel.tile_cumsum(
                vals.reshape(b * t, EB), interpret=interpret
            )
        else:
            incl = jnp.cumsum(vals, axis=2).reshape(b * t, EB)
        # inclusive tile bases, then exclusive
        tot = incl[:, -1].reshape(b, t)
        bh, bl = _comp_scan(tot, jnp.zeros_like(tot))
        bh = jnp.concatenate([zrow, bh[:, :-1]], axis=1)
        bl = jnp.concatenate([zrow, bl[:, :-1]], axis=1)
        # (tile row, lane) reads from the [B*T, EB] prefix as the kernel
        # lays it out: a flat [B, T*EB] view, or a [B, T, EB] one, costs
        # the TPU compiler a relayout copy (and ~30 s of compile at 10^7
        # slots)
        row0 = jnp.arange(b, dtype=jnp.int32)[:, None] * t
        ih = jnp.where(z_hi, 0.0, incl[row0 + q_hi, l_hi])
        il = jnp.where(z_lo, 0.0, incl[row0 + q_lo, l_lo])
        return (jnp.take(bh, q_hi, axis=1) - jnp.take(bh, q_lo, axis=1)) + (
            (ih - il)
            + (jnp.take(bl, q_hi, axis=1) - jnp.take(bl, q_lo, axis=1))
        )

    return step


@functools.partial(
    jax.jit,
    static_argnames=(
        "steps", "num_vertices", "edges_hi", "normalize", "engine", "interpret"
    ),
)
def slot_walk_blocked(
    dst: jnp.ndarray,
    block_lo: jnp.ndarray,
    block_hi: jnp.ndarray,
    steps: int,
    num_vertices: int,
    *,
    edges_hi: int,
    normalize: bool = False,
    engine: str = "xla",
    interpret: bool = False,
) -> jnp.ndarray:
    """Scatter-free walk step via block-interval prefix sums.

    See ``make_blocked_step`` for the hierarchical two-level prefix and
    the TwoSum compensation that keeps skewed-magnitude rows exact.  No
    ``slot_rows`` operand: dead slots are masked from ``dst`` alone.
    """
    gidx_p = _prep_gidx(dst, num_vertices, edges_hi)
    step = make_blocked_step(
        gidx_p, block_lo, block_hi, num_vertices,
        engine=engine, interpret=interpret,
    )
    visits = jnp.ones((1, num_vertices), jnp.float32)

    def body(visits, _):
        nxt = step(visits)
        if normalize:
            nxt = nxt / jnp.maximum(
                jnp.max(nxt, axis=1, keepdims=True), 1.0
            )
        return nxt, None

    visits, _ = jax.lax.scan(body, visits, None, length=steps)
    return visits[0]


# ---------------------------------------------------------------------------
# multi-walk batching: B visit vectors through the same step programs
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("steps", "num_vertices", "edges_hi", "normalize")
)
def slot_walk_multi_xla(
    dst: jnp.ndarray,
    slot_rows: jnp.ndarray,
    visits0: jnp.ndarray,
    steps: int,
    num_vertices: int,
    *,
    edges_hi: int,
    normalize: bool = False,
) -> jnp.ndarray:
    """Batched walk: ``visits0`` [B, V] -> [B, V], one fused step loop.

    The gather broadcasts over the batch axis and the per-step
    segment-sum runs once on the transposed [E, B] values, so B walks
    cost one scan instead of B dispatch loops.
    """
    sink = num_vertices
    rows_p, gidx_p = _prep(dst, slot_rows, num_vertices, edges_hi)
    rows_f = rows_p.reshape(-1)
    gidx_f = gidx_p.reshape(-1)
    zcol = jnp.zeros((visits0.shape[0], 1), jnp.float32)

    def body(visits, _):
        vals = jnp.concatenate([visits, zcol], axis=1)[:, gidx_f]  # [B, E]
        nxt = jax.ops.segment_sum(vals.T, rows_f, num_segments=sink + 1)[
            :num_vertices
        ].T
        if normalize:
            nxt = nxt / jnp.maximum(
                jnp.max(nxt, axis=1, keepdims=True), 1.0
            )
        return nxt, None

    visits, _ = jax.lax.scan(body, visits0, None, length=steps)
    return visits


# ---------------------------------------------------------------------------
# sparse frontier step: a seeded walk reads the slots it hits, not all E
# ---------------------------------------------------------------------------
#: frontier widths the membership compare is compiled at; a step takes the
#: narrowest that holds its frontier.  Widths past the first that covers
#: every vertex are dropped.
FRONTIER_WIDTHS = (32, 128, 512)
#: hit slots a sparse step carries per vertex of the widest frontier
HITS_PER_VERTEX = 4


def _sparse_caps(num_vertices: int, t: int):
    """``(widths, hit_tiles, hit_slots)`` for a walk of ``num_vertices``
    over ``t`` tiles.  Every hit tile holds a hit slot, so ``hit_tiles``
    is the slot cap clipped to the arena's tile count."""
    widths = []
    for w in FRONTIER_WIDTHS:
        widths.append(w)
        if w >= num_vertices:
            break
    slots = HITS_PER_VERTEX * widths[-1]
    return tuple(widths), min(slots, t), slots


def _cumsum(x):
    """Inclusive int32 cumsum of a 1-D array, blocked by EB: an
    iota-compare sum within each block, the block totals recursively.
    ``jnp.cumsum`` makes the TPU compiler's time grow with the axis
    (seconds at 10^5 entries, in every step of the program)."""
    n = x.shape[0]
    m = -(-n // EB)
    blk = jnp.pad(x.astype(jnp.int32), (0, m * EB - n)).reshape(m, EB)
    tri = jnp.arange(EB)[:, None] <= jnp.arange(EB)[None, :]
    blk = jnp.sum(jnp.where(tri, blk[:, :, None], 0), axis=1)
    if m > 1:
        blk = blk + (_cumsum(blk[:, -1]) - blk[:, -1])[:, None]
    return blk.reshape(-1)[:n]


def _compact(count, n: int):
    """The first ``n`` positions where the running count ``count`` steps
    up, and which of them exist: ``(pos [n], ok [n])``."""
    k = jnp.arange(n, dtype=count.dtype)
    pos = jnp.searchsorted(count, k, side="right")
    return jnp.minimum(pos, count.shape[0] - 1).astype(jnp.int32), k < count[-1]


def _frontier(visits0, width: int):
    """Union support of ``visits0`` [B, V] as ids [width] (padded with
    ``V + 1``), values [B, width] and its size (which may exceed
    ``width``).  Two levels: per-128-vertex tile counts, then the
    vertices of the non-zero tiles alone."""
    nv = visits0.shape[1]
    vt = -(-nv // EB)
    nz = jnp.pad(jnp.any(visits0 != 0, axis=0), (0, vt * EB - nv))
    nz = nz.reshape(vt, EB)
    counts = jnp.sum(nz, axis=1, dtype=jnp.int32)
    tile, tile_ok = _compact(_cumsum(counts > 0), min(width, vt))
    member = (nz[tile] & tile_ok[:, None]).reshape(-1)
    at, ok = _compact(_cumsum(member), width)
    vid = jnp.minimum(tile[at // EB] * EB + at % EB, nv - 1)
    fid = jnp.where(ok, vid, nv + 1)
    fv = jnp.where(ok[None, :], visits0[:, vid], 0.0)
    return fid, fv, jnp.sum(counts)


def _positions(gidx_p, fid, nf, widths, *, engine: str, interpret: bool):
    """Each slot's position in the frontier ``fid``, or -1: [T, EB].

    Pallas compares the gather plane with the narrowest width that holds
    the ``nf`` live ids; XLA looks each slot up in the sorted ids."""
    if engine == "pallas":
        def compare(w):
            return lambda f: _kernel.frontier_positions(
                gidx_p, f[:w], interpret=interpret
            )

        pick = jnp.sum(nf > jnp.asarray(widths[:-1], jnp.int32))
        return jax.lax.switch(pick, [compare(w) for w in widths], fid)
    order = jnp.argsort(fid).astype(jnp.int32)
    ids = fid[order]
    i = jnp.minimum(jnp.searchsorted(ids, gidx_p), fid.shape[0] - 1)
    return jnp.where(ids[i] == gidx_p, order[i], -1)


def make_frontier_step(gidx_p, slot_rows, block_lo, block_hi,
                       num_vertices: int, *, normalize: bool = False,
                       engine: str = "xla", interpret: bool = False):
    """Build the sparse walk step over a frontier of at most the widest
    width: ``(fid, fv, nf, ok) -> (fid, fv, nf, ok)``.

    Reads the gather plane once (the membership compare), then only the
    hit slots: per-tile hit counts compact the hit tiles, those compact
    the hit slots, each slot's owner comes from ``slot_rows``, and the
    values combine by owner.  An owner's slots are one contiguous block,
    so its hits are adjacent in slot order and the combine is a run sum
    (a one-hot matmul), with no sort and no scatter.  Dead and pad slots
    gather ``num_vertices`` and never hit.  ``ok`` drops, and the state
    stops being exact, when the hits exceed the slot cap, the next
    frontier exceeds the widest width, or a hit slot's owner does not
    hold it in its ``[lo, hi)`` block.
    """
    widths, hit_tiles, hit_slots = _sparse_caps(
        num_vertices, gidx_p.shape[0]
    )
    width = widths[-1]
    cap_e = slot_rows.shape[0]

    @jax.named_scope("slot_walk.frontier_step")
    def step(fid, fv, nf, ok):
        pos = _positions(gidx_p, fid, nf, widths, engine=engine,
                         interpret=interpret)
        counts = jnp.sum(pos >= 0, axis=1, dtype=jnp.int32)
        tile, tile_ok = _compact(_cumsum(counts > 0), hit_tiles)
        pos_h = jnp.where(tile_ok[:, None], pos[tile], -1).reshape(-1)
        at, hit = _compact(_cumsum(pos_h >= 0), hit_slots)
        slot = tile[at // EB] * EB + at % EB
        own = slot_rows[jnp.minimum(slot, cap_e - 1)]
        oc = jnp.clip(own, 0, num_vertices - 1)
        held = (
            (own == oc) & (block_lo[oc] <= slot) & (slot < block_hi[oc])
        )
        prev = jnp.concatenate([jnp.full((1,), -1, own.dtype), own[:-1]])
        start = hit & (own != prev)
        run = _cumsum(start) - 1
        onehot = hit[:, None] & (
            run[:, None] == jnp.arange(width, dtype=jnp.int32)[None, :]
        )
        vals = jnp.where(hit[None, :], fv[:, jnp.maximum(pos_h[at], 0)], 0.0)
        nxt = jnp.dot(vals, onehot.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
        nid = jnp.min(jnp.where(onehot, own[:, None], num_vertices + 1),
                      axis=0)
        if normalize:
            nxt = nxt / jnp.maximum(jnp.max(nxt, axis=1, keepdims=True), 1.0)
        n_next = jnp.sum(start, dtype=jnp.int32)
        ok = ok & (jnp.sum(counts) <= hit_slots) & (n_next <= width) & ~jnp.any(
            hit & ~held
        )
        return nid, nxt, n_next, ok

    return step


@functools.partial(
    jax.jit,
    static_argnames=(
        "steps", "num_vertices", "edges_hi", "normalize", "engine", "interpret"
    ),
)
def slot_walk_multi_blocked(
    dst: jnp.ndarray,
    slot_rows: jnp.ndarray,
    block_lo: jnp.ndarray,
    block_hi: jnp.ndarray,
    visits0: jnp.ndarray,
    steps: int,
    num_vertices: int,
    *,
    edges_hi: int,
    normalize: bool = False,
    engine: str = "xla",
    interpret: bool = False,
):
    """Batched walk: visits0 [B, V] -> ([B, V], steps run sparse).

    A batch whose union support fits the widest frontier width walks in
    the sparse form (``make_frontier_step``), touching only the slots
    its frontier hits; the answer is written into a zero [B, V].  A
    batch that does not fit, or whose frontier outgrows the caps at any
    step, runs the dense scatter-free prefix-sum scan
    (``make_blocked_step``) from ``visits0`` instead, in the same
    program.  The dense step is natively batched — the interval index
    arithmetic is shared, only the gathered values and prefix sums carry
    a batch dim (the Pallas intra-tile cumsum sees B*T independent tiles
    of the same kernel).  The second result, an int32 scalar, is
    ``steps`` when the sparse form answered and 0 when the dense did.
    """
    gidx_p = _prep_gidx(dst, num_vertices, edges_hi)
    step = make_blocked_step(
        gidx_p, block_lo, block_hi, num_vertices,
        engine=engine, interpret=interpret,
    )

    def dense():
        def body(visits, _):
            nxt = step(visits)
            if normalize:
                nxt = nxt / jnp.maximum(
                    jnp.max(nxt, axis=1, keepdims=True), 1.0
                )
            return nxt, None

        visits, _ = jax.lax.scan(body, visits0, None, length=steps)
        return visits

    sparse_step = make_frontier_step(
        gidx_p, slot_rows, block_lo, block_hi, num_vertices,
        normalize=normalize, engine=engine, interpret=interpret,
    )
    width = _sparse_caps(num_vertices, gidx_p.shape[0])[0][-1]
    fid, fv, nf = _frontier(visits0, width)

    def body(state, _):
        live = state[3] & (state[2] > 0)
        return jax.lax.cond(live, sparse_step, lambda *s: s, *state), None

    (fid, fv, _nf, ok), _ = jax.lax.scan(
        body, (fid, fv, nf, nf <= width), None, length=steps,
    )
    visits = jax.lax.cond(
        ok,
        lambda: jnp.zeros_like(visits0).at[:, fid].set(fv, mode="drop"),
        dense,
    )
    return visits, jnp.where(ok, steps, 0).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("steps", "num_vertices", "edges_hi", "normalize", "interpret"),
)
def slot_walk_multi_pallas(
    dst: jnp.ndarray,
    slot_rows: jnp.ndarray,
    visits0: jnp.ndarray,
    steps: int,
    num_vertices: int,
    *,
    edges_hi: int,
    normalize: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """Batched Pallas walk: stack the B walks' tiles into one kernel call.

    ``rows`` are identical per walk, so tiling them B times turns the
    batch into B*T independent tiles of the SAME one-hot-rank kernel —
    one ``pallas_call`` per step regardless of B.  The seam fold then
    segments with a per-walk offset (walk b's rows live in segment ids
    ``[b*(sink+1), (b+1)*(sink+1))``).
    """
    sink = num_vertices
    rows_p, gidx_p = _prep(dst, slot_rows, num_vertices, edges_hi)
    t = rows_p.shape[0]
    b = visits0.shape[0]
    rows_t = jnp.tile(rows_p, (b, 1))  # [B*T, EB]
    zcol = jnp.zeros((b, 1), jnp.float32)
    offs = jnp.repeat(
        jnp.arange(b, dtype=jnp.int32) * (sink + 1), t
    )[:, None]  # [B*T, 1]

    def body(visits, _):
        vals = jnp.concatenate([visits, zcol], axis=1)[:, gidx_p]  # [B,T,EB]
        part, rank = _kernel.slot_walk_partials(
            rows_t, vals.reshape(b * t, EB), sink=sink, interpret=interpret
        )
        ids = jnp.minimum(rank, sink) + offs
        nxt = jax.ops.segment_sum(
            part.reshape(-1), ids.reshape(-1), num_segments=b * (sink + 1)
        ).reshape(b, sink + 1)[:, :num_vertices]
        if normalize:
            nxt = nxt / jnp.maximum(
                jnp.max(nxt, axis=1, keepdims=True), 1.0
            )
        return nxt, None

    visits, _ = jax.lax.scan(body, visits0, None, length=steps)
    return visits


def slot_walk(
    dst: jnp.ndarray,
    slot_rows: jnp.ndarray,
    steps: int,
    num_vertices: int,
    *,
    edges_hi: int | None = None,
    backend: str = "auto",
    block_lo: jnp.ndarray | None = None,
    block_hi: jnp.ndarray | None = None,
    normalize: bool = False,
    interpret: bool = False,
    visits0: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """k-step reverse walk over the slotted arena's live prefix.

    ``edges_hi`` bounds the slots processed (callers pass the arena bump,
    quantized); None means the whole buffer.  ``backend`` is ``auto``
    (pallas on TPU, xla elsewhere), ``pallas`` or ``xla``.  When the
    caller can supply per-vertex block intervals (``block_lo`` /
    ``block_hi``, int32 [num_vertices], lo == hi == 0 for blockless
    rows), the xla backend upgrades to the scatter-free prefix-sum
    formulation.  ``visits0`` switches to multi-walk batching: a
    [B, num_vertices] f32 stack of initial visit vectors walks together
    through one fused step loop, returning [B, num_vertices].
    """
    if edges_hi is None:
        edges_hi = dst.shape[0]
    edges_hi = min(int(edges_hi), dst.shape[0])
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown slot_walk backend: {backend!r}")
    if visits0 is not None:
        if visits0.ndim != 2 or visits0.shape[1] != num_vertices:
            raise ValueError(
                "visits0 must be [num_walks, num_vertices], got "
                f"{visits0.shape}"
            )
        visits0 = jnp.asarray(visits0, jnp.float32)

    # dispatch runs through the health-gated fallback chain (DESIGN.md
    # §13): a failing backend is retried once, then the call degrades
    # pallas → xla → host ref under the per-backend circuit breaker
    # instead of killing the stream
    def _dispatch(b: str) -> jnp.ndarray:
        if b == "ref":
            return _ref.slot_walk_host(
                dst, slot_rows, steps, num_vertices, edges_hi=edges_hi,
                block_lo=block_lo, block_hi=block_hi, normalize=normalize,
                visits0=visits0,
            )
        if visits0 is not None:
            if block_lo is not None and block_hi is not None:
                out, sparse = slot_walk_multi_blocked(
                    dst, slot_rows, block_lo, block_hi, visits0, steps,
                    num_vertices, edges_hi=edges_hi, normalize=normalize,
                    engine=b, interpret=interpret,
                )
                _last.sparse_steps = sparse
                return out
            if b == "pallas":
                return slot_walk_multi_pallas(
                    dst, slot_rows, visits0, steps, num_vertices,
                    edges_hi=edges_hi, normalize=normalize,
                    interpret=interpret,
                )
            return slot_walk_multi_xla(
                dst, slot_rows, visits0, steps, num_vertices,
                edges_hi=edges_hi, normalize=normalize,
            )
        if block_lo is not None and block_hi is not None:
            return slot_walk_blocked(
                dst, block_lo, block_hi, steps, num_vertices,
                edges_hi=edges_hi, normalize=normalize, engine=b,
                interpret=interpret,
            )
        if b == "pallas":
            return slot_walk_pallas(
                dst, slot_rows, steps, num_vertices,
                edges_hi=edges_hi, normalize=normalize, interpret=interpret,
            )
        return slot_walk_xla(
            dst, slot_rows, steps, num_vertices,
            edges_hi=edges_hi, normalize=normalize,
        )

    _last.sparse_steps = None
    out, _used = _fb.run_chain("slot_walk", backend, _dispatch)
    return out


def take_sparse_steps():
    """The steps that this thread's last ``slot_walk`` ran in the sparse
    frontier form, as the device int32 scalar its program returned (None
    where no batched blocked program answered), and forget it.  Fetch it
    with the walk's result: it is ready when the result is."""
    sparse, _last.sparse_steps = getattr(_last, "sparse_steps", None), None
    return sparse


def slot_walk_image(
    image,
    steps: int,
    *,
    backend: str = "auto",
    normalize: bool = False,
    interpret: bool = False,
    visits0: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Image-input entry point: walk a ``core.walk_image.WalkImage``.

    The image supplies the full operand set — packed buffers, quantized
    prefix bound, per-vertex block intervals — so every representation's
    walk lands on the same engine with the same jit-shape policy.  All
    backends now take the scatter-free interval formulation (DESIGN.md
    §12): ``slot_rows`` is folded into the [lo, hi) geometry, so the
    step loop's per-slot operand set is the gather plane alone — Pallas
    runs the intra-tile prefix level on the MXU, XLA on the vector unit.
    """
    block_lo, block_hi = image.device_blocks()
    return slot_walk(
        image.dst,
        image.rows,
        steps,
        image.nv,
        edges_hi=image.edges_hi(),
        backend=backend,
        block_lo=block_lo,
        block_hi=block_hi,
        normalize=normalize,
        interpret=interpret,
        visits0=visits0,
    )
