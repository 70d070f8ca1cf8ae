"""Pallas TPU kernel: fused reverse-walk tile reduction over the slotted arena.

The k-step reverse walk (paper Alg 13) is, per step, a segment-sum of
gathered ``visits`` values into the owning row of every live edge slot.  On
the slotted DiGraph buffer each vertex's block is *contiguous*, so within a
128-slot tile the row ids form contiguous runs (a run per block, dead-slot
tails mapped to ``sink``).  That lets each tile be reduced with one MXU
matmul: count the run-change flags into local *ranks*, build the
[slot, rank] one-hot matrix, and fold ``vals @ onehot`` into per-rank
partial sums — O(CAP_E/128) matmuls instead of CAP_E scalar scatters.  A
tiny cross-tile segment-sum outside the kernel merges tile-seam runs
(ops.py), and the step loop is a ``lax.scan`` *around* the kernel so
``visits`` never leaves the device between steps.

Every kernel here takes a block of many 128-slot tiles per grid step
(``kernels.row_tiling``: a multiple of 8 rows, or the whole array), and
prefix counts are iota-compare sums or triangular matmuls — the TPU
lowering has no ``cumsum``.  Matmuls that carry data values run at
``HIGHEST`` precision so the MXU keeps f32 accuracy.

Inputs (ops.py pads the live prefix to whole tiles):
  rows [T, EB]  int32 slot owners; dead/pad slots carry ``sink``
  vals [T, EB]  f32 gathered visits, zero on dead/pad slots
Outputs:
  partials  [T, EB]  per-tile per-rank sums
  rank_rows [T, EB]  global row id per rank (``sink`` for dead ranks)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import row_tiling

HIGHEST = jax.lax.Precision.HIGHEST
#: tiles per grid step of the intra-tile prefix (512 KiB of f32 per block)
CUMSUM_ROWS = 1024
#: tiles per grid step of the one-hot-rank reduction (looped row by row)
PARTIAL_ROWS = 64


def _cumsum_kernel(vals_ref, out_ref):
    eb = vals_ref.shape[-1]
    # inclusive prefix within each tile as ONE MXU matmul against the
    # upper-triangular ones matrix: out[r, j] = Σ_{k<=j} v[r, k]
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (eb, eb), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (eb, eb), 1)
    ).astype(jnp.float32)
    out_ref[...] = jnp.dot(
        vals_ref[...], tri, precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )


def tile_cumsum(vals: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Per-tile inclusive cumsum: vals [T, EB] -> [T, EB] (MXU matmul).

    The intra-tile level of the hierarchical walk prefix (DESIGN.md §12):
    each 128-slot tile's running sum is one row of a [R,128]@[128,128]
    triangular matmul, so the scatter-free interval walk needs no
    per-slot owner operand on the Pallas backend either — the inter-tile
    base scan and the [lo, hi) differencing stay in the XLA glue
    (ops.py).  ``T`` should be a multiple of 8 (ops.py pads the tile
    planes); other counts pay one padding copy here.  Plain function
    (not jitted) so callers can inline it into fused programs.
    """
    t, eb = vals.shape
    t_pad, r = row_tiling(t, CUMSUM_ROWS)
    if t_pad != t:
        vals = jnp.pad(vals, ((0, t_pad - t), (0, 0)))
    spec = pl.BlockSpec((r, eb), lambda i: (i, 0))
    out = pl.pallas_call(
        _cumsum_kernel,
        grid=(t_pad // r,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, eb), jnp.float32),
        interpret=interpret,
    )(vals)
    return out[:t] if t_pad != t else out


def _kernel(rows_ref, vals_ref, part_ref, rank_ref, *, sink: int):
    n_rows, eb = rows_ref.shape
    i0 = jax.lax.broadcasted_iota(jnp.int32, (eb, eb), 0)
    i1 = jax.lax.broadcasted_iota(jnp.int32, (eb, eb), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, eb), 1)

    def one_tile(r, carry):
        rows = rows_ref[pl.ds(r, 1), :]          # [1, EB]
        vals = vals_ref[pl.ds(r, 1), :]          # [1, EB]
        rows_c = rows.reshape(eb, 1)
        # prev[j] = rows[j-1]: a shift-matrix select-reduce (exact int32)
        prev = jnp.sum(jnp.where(i0 == i1 - 1, rows_c, 0), axis=0,
                       keepdims=True)
        run_start = (rows != prev) | (lane == 0)  # block boundaries
        # rank[j] = #run starts in [1, j]: an iota-compare prefix count
        rank = jnp.sum(
            jnp.where(i0 <= i1, run_start.astype(jnp.int32).reshape(eb, 1), 0),
            axis=0, keepdims=True,
        ) - 1                                    # [1, EB] in [0, EB)
        oh = i1 == rank.reshape(eb, 1)           # [slot, rank]
        part_ref[pl.ds(r, 1), :] = jnp.dot(
            vals, oh.astype(jnp.float32), precision=HIGHEST,
            preferred_element_type=jnp.float32,
        )
        rr = jnp.max(jnp.where(oh & (rows_c < sink), rows_c, -1), axis=0,
                     keepdims=True)
        rank_ref[pl.ds(r, 1), :] = jnp.where(rr >= 0, rr, sink)
        return carry

    jax.lax.fori_loop(0, n_rows, one_tile, 0)


@functools.partial(jax.jit, static_argnames=("sink", "interpret"))
def slot_walk_partials(
    rows: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    sink: int,
    interpret: bool = False,
):
    """One walk step's tile reduction: rows/vals [T, EB] -> (partials, rank_rows).

    ``T`` should be a multiple of 8 (ops.py pads); other counts pay one
    padding copy here, with pad tiles owned by ``sink``.
    """
    t, eb = rows.shape
    t_pad, r = row_tiling(t, PARTIAL_ROWS)
    if t_pad != t:
        rows = jnp.pad(rows, ((0, t_pad - t), (0, 0)), constant_values=sink)
        vals = jnp.pad(vals, ((0, t_pad - t), (0, 0)))
    kern = functools.partial(_kernel, sink=sink)
    spec = pl.BlockSpec((r, eb), lambda i: (i, 0))
    part, rank = pl.pallas_call(
        kern,
        grid=(t_pad // r,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((t_pad, eb), jnp.float32),
            jax.ShapeDtypeStruct((t_pad, eb), jnp.int32),
        ],
        interpret=interpret,
    )(rows, vals)
    return part[:t], rank[:t]
