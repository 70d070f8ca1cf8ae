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

Both only process ``edges_hi`` slots (the arena's bump prefix, rounded up
to a power of two by the caller so the jit cache stays O(log CAP_E))
instead of the full CAP_E buffer — on updated graphs that alone is the
difference between walking the paper's live edges and walking every dead
SENTINEL lane the allocator ever reserved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import util
from .. import fallback as _fb
from . import kernel as _kernel
from . import ref as _ref

SENTINEL = util.SENTINEL
EB = 128  # slots per tile (MXU-native)


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


@functools.partial(
    jax.jit,
    static_argnames=(
        "steps", "num_vertices", "edges_hi", "normalize", "engine", "interpret"
    ),
)
def slot_walk_multi_blocked(
    dst: jnp.ndarray,
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
) -> jnp.ndarray:
    """Batched scatter-free prefix-sum walk: visits0 [B, V] -> [B, V].

    The blocked step is natively batched — the interval index arithmetic
    is shared, only the gathered values and prefix sums carry a batch
    dim (the Pallas intra-tile cumsum sees B*T independent tiles of the
    same kernel).
    """
    gidx_p = _prep_gidx(dst, num_vertices, edges_hi)
    step = make_blocked_step(
        gidx_p, block_lo, block_hi, num_vertices,
        engine=engine, interpret=interpret,
    )

    def body(visits, _):
        nxt = step(visits)
        if normalize:
            nxt = nxt / jnp.maximum(
                jnp.max(nxt, axis=1, keepdims=True), 1.0
            )
        return nxt, None

    visits, _ = jax.lax.scan(body, visits0, None, length=steps)
    return visits


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
                return slot_walk_multi_blocked(
                    dst, block_lo, block_hi, visits0, steps,
                    num_vertices, edges_hi=edges_hi, normalize=normalize,
                    engine=b, interpret=interpret,
                )
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

    out, _used = _fb.run_chain("slot_walk", backend, _dispatch)
    return out


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
