"""Pallas TPU kernel: fused sorted-merge of batch runs into arena rows.

Each grid step merges a block of touched rows (``kernels.row_tiling``:
a multiple of 8, or all of them), one row at a time: the row's gathered
block prefix ``[1, W]`` with its batch run ``[1, K]`` (both ascending,
SENTINEL-padded; at most one op per key, guaranteed by UpdatePlan).  The
merge is scatter-free — TPUs have no scatter unit, so every output
element is *ranked* instead of moved — and works on 128-lane chunks of
the row so no temporary is wider than ``[max(K, 128), 128]``:

  membership   [K, 128] equality tiles between run values and row
               values (VPU compares),
  ranks        survivors keep their order plus the count of new inserts
               below them; new inserts symmetrically — prefix counts are
               iota-compare sums (the TPU lowering has no ``cumsum``),
  placement    ``[slot, position]`` one-hot select-reduces fold values
               into their final positions.  A survivor moves at most K
               lanes (K deletes before it, or K inserts), so output
               chunk c only reads source chunks within ``ceil(K/128)``
               of c.

Placement sums one int32 id (or f32 weight) per output lane, so it is
exact for every int32 vertex id.  Rows wider than ``MAX_WIDTH`` take the
XLA formulation (``ops.merge_rows``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import util
from .. import row_tiling

SENTINEL = util.SENTINEL
#: lanes per chunk of a row
EB = 128
#: widest row class (and run width) the kernel merges
MAX_WIDTH = 1024
#: rows per grid step
ROWS = 8


def _excl_prefix(x, n: int):
    """Exclusive prefix count of an int32 row ``x`` [1, n]."""
    i0 = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    i1 = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(i0 < i1, x.reshape(n, 1), 0), axis=0,
                   keepdims=True)


def _merge_row(d_ch, w_ch, bd, bw, bdel, deg):
    """One row: d/w as nb chunks [1, cw], run bd/bw/bdel [1, K], deg [1, 1].

    Only int32/f32 values change between row and column layouts here:
    the TPU lowering cannot reshape a bool vector.
    """
    nb, cw, kk = len(d_ch), d_ch[0].shape[1], bd.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, cw), 1)
    bd_c = bd.reshape(kk, 1)            # run values as a column
    bw_c = bw.reshape(kk, 1)
    bdel_c = bdel.reshape(kk, 1) != 0
    live = [lane + c * cw < deg for c in range(nb)]

    # membership: eq[k, s] — run op k hits live row slot s
    found = jnp.zeros((kk, 1), jnp.bool_)
    killed, w2 = [], []
    for c in range(nb):
        eq = (bd_c == d_ch[c]) & live[c]
        found = found | jnp.any(eq, axis=1, keepdims=True)
        # deletions kill their row slot; upserts replace its weight
        killed.append(jnp.any(eq & bdel_c, axis=0, keepdims=True))
        upd = eq & ~bdel_c
        w_up = jnp.sum(jnp.where(upd, bw_c, 0.0), axis=0, keepdims=True)
        w2.append(jnp.where(jnp.any(upd, axis=0, keepdims=True), w_up,
                            w_ch[c]))
    new_ins = ~found & ~bdel_c & (bd_c != SENTINEL)    # [K, 1]

    # ranks: survivors shift up by the new inserts below them, and vice
    # versa — both counts fall out of the same comparison tiles.  The
    # ``~below & (bd != d)`` spelling (not ``d < bd``) keeps SENTINEL row
    # padding from counting: it equals the run padding value.
    surv, pos_surv = [], []
    surv_before = jnp.zeros((kk, 1), jnp.int32)
    n_surv = jnp.zeros((1, 1), jnp.int32)
    for c in range(nb):
        s = live[c] & ~killed[c]
        si = s.astype(jnp.int32)
        below = bd_c < d_ch[c]                           # [K, cw]
        ins_before = jnp.sum((below & new_ins).astype(jnp.int32), axis=0,
                             keepdims=True)
        pos_surv.append(n_surv + _excl_prefix(si, cw) + ins_before)
        surv.append(si.reshape(cw, 1) != 0)
        surv_before = surv_before + jnp.sum(
            (~below & (bd_c != d_ch[c]) & s).astype(jnp.int32), axis=1,
            keepdims=True,
        )
        n_surv = n_surv + jnp.sum(si, axis=1, keepdims=True)
    ins_i = new_ins.astype(jnp.int32)
    k0 = jax.lax.broadcasted_iota(jnp.int32, (kk, kk), 0)
    k1 = jax.lax.broadcasted_iota(jnp.int32, (kk, kk), 1)
    ins_rank = jnp.sum(jnp.where(k1 < k0, ins_i.reshape(1, kk), 0), axis=1,
                       keepdims=True)                    # [K, 1]
    pos_ins = ins_rank + surv_before
    count = n_surv + jnp.sum(ins_i, axis=0, keepdims=True)   # [1, 1]

    # placement: one-hot [slot, position] select-reduces, band-limited
    band = -(-kk // cw)
    out_d, out_w = [], []
    for c in range(nb):
        pos = c * cw + lane                              # [1, cw]
        oh_i = (pos_ins == pos) & new_ins                # [K, cw]
        acc_d = jnp.sum(jnp.where(oh_i, bd_c, 0), axis=0, keepdims=True)
        acc_w = jnp.sum(jnp.where(oh_i, bw_c, 0.0), axis=0, keepdims=True)
        for c2 in range(max(c - band, 0), min(c + band, nb - 1) + 1):
            oh = (pos_surv[c2].reshape(cw, 1) == pos) & surv[c2]
            acc_d = acc_d + jnp.sum(
                jnp.where(oh, d_ch[c2].reshape(cw, 1), 0), axis=0,
                keepdims=True,
            )
            acc_w = acc_w + jnp.sum(
                jnp.where(oh, w2[c2].reshape(cw, 1), 0.0), axis=0,
                keepdims=True,
            )
        keep = pos < count
        out_d.append(jnp.where(keep, acc_d, SENTINEL))
        out_w.append(jnp.where(keep, acc_w, 0.0))
    return out_d, out_w, count


def _kernel(deg_ref, d_ref, w_ref, bd_ref, bw_ref, bdel_ref,
            od_ref, ow_ref, cnt_ref):
    # rows arrive as [R, nb, cw]: the TPU lowering loads a dynamic row's
    # chunk at a static sublane, but not at a nonzero lane offset
    chunks = [pl.ds(c, 1) for c in range(d_ref.shape[1])]

    def one_row(r, carry):
        row = pl.ds(r, 1)
        out_d, out_w, count = _merge_row(
            [d_ref[r, ch, :] for ch in chunks],
            [w_ref[r, ch, :] for ch in chunks],
            bd_ref[row, :], bw_ref[row, :], bdel_ref[row, :],
            deg_ref[row, :],
        )
        for ch, od, ow in zip(chunks, out_d, out_w):
            od_ref[r, ch, :] = od
            ow_ref[r, ch, :] = ow
        cnt_ref[row, :] = count
        return carry

    jax.lax.fori_loop(0, d_ref.shape[0], one_row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_rows_pallas(
    d_rows: jnp.ndarray,
    w_rows: jnp.ndarray,
    degs: jnp.ndarray,
    b_dst: jnp.ndarray,
    b_wgt: jnp.ndarray,
    b_del: jnp.ndarray,
    *,
    interpret: bool = False,
):
    """Row-tile merge: [A, W] rows × [A, K] runs -> (out_d, out_w, counts).

    ``W`` is below 128 or a multiple of it, and ``W, K <= MAX_WIDTH``.
    Row counts that are not a multiple of 8 (beyond one block) pad with
    empty rows, which merge to nothing.
    """
    a, w = d_rows.shape
    k = b_dst.shape[1]
    if w > MAX_WIDTH or k > MAX_WIDTH or (w > EB and w % EB):
        raise ValueError(f"merge_rows_pallas: unsupported W={w}, K={k}")
    a_pad, r = row_tiling(a, ROWS)
    cw = min(w, EB)
    nb = w // cw
    deg2 = degs.reshape(a, 1).astype(jnp.int32)
    ops = (deg2, d_rows, w_rows, b_dst, b_wgt, b_del)
    if a_pad != a:
        fills = (0, SENTINEL, 0.0, SENTINEL, 0.0, 0)
        ops = tuple(
            jnp.pad(x, ((0, a_pad - a), (0, 0)), constant_values=f)
            for x, f in zip(ops, fills)
        )
    deg2, d3, w3 = ops[0], ops[1].reshape(a_pad, nb, cw), ops[2].reshape(
        a_pad, nb, cw
    )
    row_spec = pl.BlockSpec((r, nb, cw), lambda i: (i, 0, 0))
    run_spec = pl.BlockSpec((r, k), lambda i: (i, 0))
    one_spec = pl.BlockSpec((r, 1), lambda i: (i, 0))
    out_d, out_w, counts = pl.pallas_call(
        _kernel,
        grid=(a_pad // r,),
        in_specs=[one_spec, row_spec, row_spec, run_spec, run_spec, run_spec],
        out_specs=[row_spec, row_spec, one_spec],
        out_shape=[
            jax.ShapeDtypeStruct((a_pad, nb, cw), jnp.int32),
            jax.ShapeDtypeStruct((a_pad, nb, cw), jnp.float32),
            jax.ShapeDtypeStruct((a_pad, 1), jnp.int32),
        ],
        interpret=interpret,
    )(deg2, d3, w3, *ops[3:])
    return (
        out_d.reshape(a_pad, w)[:a], out_w.reshape(a_pad, w)[:a],
        counts[:a, 0],
    )
