"""Pallas TPU kernel: partitioned per-vertex degree counting (paper Alg 5).

The paper's loader counts degrees in parallel partitions and merges the
partial histograms; on TPU the partition becomes an *edge block* and the
merge becomes grid accumulation.  Grid = (vertex blocks × edge blocks):
each step folds a block of 128-wide src tiles into a block of 128-wide
vertex-id rows, one tile at a time, as a one-hot matmul —
``[row, edge] @ [edge, lane]`` counts every edge at (its id // 128, its
id % 128) — so the histogram is built with no scatters (TPU scatters
serialize; dense compare + matmul tiles don't).  Counts are sums of 0/1
products, exact in f32 for any block size used here.

Ids are compared as int32, so every int32 vertex id counts exactly.

Inputs (ops.py pads to whole tiles):
  src [T, EB] int32 edge sources; pad slots carry an id outside [0, nv)
Output:
  degrees [NV] int32, NV a multiple of the 128-lane vertex tile
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import row_tiling

#: edge-tile / vertex-tile width (one VPU lane row)
EB = 128
#: vertex rows / edge tiles per grid step
VERTEX_ROWS = 64
EDGE_ROWS = 64


def _kernel(src_ref, deg_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        deg_ref[...] = jnp.zeros_like(deg_ref)

    vr, eb = deg_ref.shape
    base = pl.program_id(0) * (vr * eb)   # first vertex id of this block
    row_id = jax.lax.broadcasted_iota(jnp.int32, (vr, eb), 0)
    lane_id = jax.lax.broadcasted_iota(jnp.int32, (eb, eb), 1)

    def one_tile(r, acc):
        off = src_ref[pl.ds(r, 1), :] - base           # [1, EB] edge tile
        ok = (off >= 0) & (off < vr * eb)
        rows = (row_id == off // eb) & ok               # [vertex row, edge]
        lanes = lane_id == (off % eb).reshape(eb, 1)    # [edge, lane]
        return acc + jnp.dot(
            rows.astype(jnp.float32), lanes.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, src_ref.shape[0], one_tile, jnp.zeros((vr, eb), jnp.float32)
    )
    deg_ref[...] += acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("nv", "interpret"))
def count_degrees_pallas(src_tiles: jnp.ndarray, *, nv: int,
                         interpret: bool = False) -> jnp.ndarray:
    """Degree histogram of src_tiles [T, EB] over ``nv`` vertices.

    ``nv`` must be a multiple of EB (ops.py rounds); pad edges must carry
    an id outside [0, nv) so they fall outside every vertex tile.
    """
    t, eb = src_tiles.shape
    assert eb == EB, f"edge tiles must be {EB} wide, got {eb}"
    nv = int(nv)
    assert nv % EB == 0, f"vertex range must be a multiple of {EB}"
    t_pad, er = row_tiling(t, EDGE_ROWS)
    if t_pad != t:
        src_tiles = jnp.pad(
            src_tiles, ((0, t_pad - t), (0, 0)), constant_values=-1
        )
    v_pad, vr = row_tiling(nv // EB, VERTEX_ROWS)
    deg = pl.pallas_call(
        _kernel,
        grid=(v_pad // vr, t_pad // er),
        in_specs=[pl.BlockSpec((er, EB), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((vr, EB), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((v_pad, EB), jnp.int32),
        interpret=interpret,
    )(src_tiles)
    return deg.reshape(-1)[:nv]
