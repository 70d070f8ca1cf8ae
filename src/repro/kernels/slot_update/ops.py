"""jit'd wrappers: one fused dispatch applies a whole mixed UpdatePlan.

``fused_apply`` lowers EVERY pow-2 width group of a plan into one
program (DESIGN.md §9/§12) — the per-group ``slot_update`` /
``merge_group`` / ``rebuild_arena`` micro-dispatch pipeline is retired:

  gather   touched rows' live prefixes into [A, W] tiles per group
           (W = the group's pow-2 width class, >= every member's
           capacity; EB=128 floor on TPU so all small classes share one
           compiled shape),
  merge    the sorted batch runs [A, K] into the sorted rows — deletes,
           weight upserts and ranked inserts in one pass (two backends:
           the Pallas one-hot-rank kernel in kernel.py, or the XLA
           bisect + rank-arithmetic formulation in ``_merge_rows_xla``),
  write    all merged groups back in one pass — either per-group
           scatters (grown rows land directly in their NEW block while
           their old block is SENTINEL-filled, so CP2AA block moves ride
           the same dispatch) or a host-mapped gather rebuild of the
           quantized bump prefix (``choose_scatter`` picks),
  walk     optionally, the k-step interval walk scan fused right behind
           the write-back (``WalkImage.walk_flush``): one dispatch per
           steady-state stream round.

Buffer donation keeps the arena update in place; every operand shape is
pow-2 bucketed so steady-state streams never recompile.  ``auto`` selects
the Pallas backend on TPU; its groups wider than the kernel's
``MAX_WIDTH`` (hub rows) merge with the XLA formulation inside the same
program, counted in ``STATS["wide_groups"]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import util
from .. import fallback as _fb
from . import kernel as _kernel
from . import ref as _refmod

SENTINEL = util.SENTINEL
#: Off-TPU write-back dispatch: arenas up to this many slots always use
#: the full-buffer gather rebuild (its dense passes beat CPU XLA scatter
#: overhead there); beyond it, batches touching < 1/10 of the arena
#: switch to per-group scatters so small updates stay O(batch).
REBUILD_MAX_CAP = 1 << 21
#: TPU row-group width floor: merges run in whole 128-slot MXU tiles.  The
#: XLA fallback instead groups rows by their exact pow-2 capacity class
#: (floor XLA_FLOOR) — CPU sort/scatter cost is linear in slots touched,
#: so padding every small class to 128 lanes would inflate it ~10x.
EB = 128
XLA_FLOOR = 8
#: Module-level dispatch counters: each ``fused_apply`` call is one device
#: program.  The sharded layer reads deltas to prove every shard's flush
#: stays at round_dispatches=1 per device (DESIGN.md §14).
#: ``wide_groups`` counts width groups a Pallas-backend program merged
#: with the XLA formulation because they exceed the kernel's MAX_WIDTH.
STATS = {"dispatches": 0, "wide_groups": 0}


def stats_snapshot() -> dict:
    return dict(STATS)


def width_floor(backend: str = "auto") -> int:
    """Row-group width floor for a (resolved) backend."""
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    return EB if backend == "pallas" else XLA_FLOOR


# ---------------------------------------------------------------------------
# merge core, XLA formulation (shape-identical to the Pallas kernel)
# ---------------------------------------------------------------------------
#: Runs wider than this take the sort-based merge; narrower runs (the
#: steady-state stream regime, K floored at 4) use the window-compaction
#: merge — no lax.sort, which costs ~4x the rest of the merge on CPU.
MERGE_WINDOW_MAX_K = 32


def _merge_rows_xla(d_rows, w_rows, degs, b_dst, b_wgt, b_del,
                    max_holes: int | None = None):
    """Scatter-free (and for narrow runs sort- and eq-tensor-free) merge.

    Rows arrive sorted (live ascending prefix, SENTINEL pad = int32
    max), and so do each row's batch ops, so op membership is a batched
    BRANCHLESS BISECT — log2(W) statically-unrolled take_along_axis
    steps over the [A, K] query set — instead of an [A, K, W] equality
    tensor, and all op effects land as [A, K]-sized scatters (~a few
    thousand indices) on the row planes:

      * deletes mark their hit lane in a ``killed`` plane,
      * upserts overwrite their hit lane's weight in place,
      * new inserts scatter value/weight/flag planes at their merged
        position (``#surviving-entries-below-key + insert-rank``).

    Final positioning is rank arithmetic (DESIGN.md §12): a delete
    punches at most ``max_holes`` holes into the sorted row (callers
    pass the group's pow-2 delete-run ceiling; the steady-state stream
    regime is 1-2), so a (holes+1)-wide select window compacts the row
    and one take_along_axis gather interleaves the inserts.  ``lax.sort``
    — which costs ~4x the rest of the merge on CPU — remains only for
    wide runs (K > MERGE_WINDOW_MAX_K, bulk hub loads), where the
    classic eq-tensor + [A, W+K] sort formulation wins.
    """
    a, w = d_rows.shape
    k = b_dst.shape[1]
    bdel = b_del != 0
    live = jnp.arange(w, dtype=jnp.int32)[None, :] < degs[:, None]

    if k > MERGE_WINDOW_MAX_K:
        # eq-tensor head + full sort (the wide-run path)
        eq = (b_dst[:, :, None] == d_rows[:, None, :]) & live[:, None, :]
        eqf = eq.astype(jnp.float32)
        not_del = (~bdel).astype(jnp.float32)
        lhs = jnp.stack(
            [bdel.astype(jnp.float32), b_wgt * not_del, not_del], axis=1
        )  # [A, 3, K]
        red = jax.lax.batch_matmul(lhs, eqf)  # [A, 3, W]
        found = (
            jax.lax.batch_matmul(
                eqf, jnp.ones((a, w, 1), jnp.float32)
            )[:, :, 0]
            > 0.0
        ) & (b_dst != SENTINEL)
        new_ins = (~found) & (~bdel) & (b_dst != SENTINEL)
        killed = red[:, 0, :] > 0.0
        d_keep = jnp.where(live & ~killed, d_rows, SENTINEL)
        w_keep = jnp.where(red[:, 2, :] > 0.0, red[:, 1, :], w_rows)
        keys = jnp.concatenate(
            [d_keep, jnp.where(new_ins, b_dst, SENTINEL)], axis=1
        )
        vals = jnp.concatenate([w_keep, b_wgt], axis=1)
        keys, vals = jax.lax.sort(
            (keys, vals), dimension=1, num_keys=1, is_stable=False
        )
        d_out = keys[:, :w]
        w_out = jnp.where(d_out != SENTINEL, vals[:, :w], 0.0)
        counts = jnp.sum(d_out != SENTINEL, axis=1).astype(jnp.int32)
        return d_out, w_out, counts

    holes = k if max_holes is None else min(int(max_holes), k)
    # --- batched branchless bisect: pos = #row entries with key < q ---
    pos = jnp.zeros((a, k), jnp.int32)
    h = w // 2
    while h >= 1:
        cand = pos + h
        at = jnp.take_along_axis(d_rows, cand - 1, axis=1)
        pos = jnp.where(at < b_dst, cand, pos)
        h //= 2
    at = jnp.take_along_axis(d_rows, jnp.minimum(pos, w - 1), axis=1)
    ilive = b_dst != SENTINEL
    found = (at == b_dst) & ilive & (pos < w)
    rowi = jnp.broadcast_to(jnp.arange(a, dtype=jnp.int32)[:, None], (a, k))

    # deletes: mark hit lanes (tiny scatter; misses dump past the plane)
    kill_idx = jnp.where(found & bdel, rowi * w + pos, a * w)
    killed = (
        jnp.zeros((a * w + 1,), bool)
        .at[kill_idx.reshape(-1)]
        .set(True)[: a * w]
        .reshape(a, w)
    )
    # upserts: weight lands in place
    up_idx = jnp.where(found & ~bdel, rowi * w + pos, a * w)
    w_keep = (
        jnp.concatenate([w_rows.reshape(-1), jnp.zeros((1,), jnp.float32)])
        .at[up_idx.reshape(-1)]
        .set(b_wgt.reshape(-1))[: a * w]
        .reshape(a, w)
    )

    keep = live & ~killed
    kept_cum = jnp.cumsum(keep.astype(jnp.int32), axis=1)
    n_kept = kept_cum[:, -1]
    kex = kept_cum - keep.astype(jnp.int32)  # kept strictly before lane i
    d_keep = jnp.where(keep, d_rows, SENTINEL)

    # new-insert placement: surviving entries below the key + run rank
    kill_cum = jnp.cumsum(killed.astype(jnp.int32), axis=1)
    kill_excl = jnp.concatenate(
        [kill_cum - killed.astype(jnp.int32), kill_cum[:, -1:]], axis=1
    )
    new_ins = ilive & ~found & ~bdel
    lt_kept = pos - jnp.take_along_axis(kill_excl, pos, axis=1)
    ins_rank = jnp.cumsum(new_ins.astype(jnp.int32), axis=1) - new_ins
    pos_ins = lt_kept + ins_rank
    ins_idx = jnp.where(
        new_ins, rowi * (w + 1) + jnp.minimum(pos_ins, w), a * (w + 1)
    ).reshape(-1)
    is_ins = (
        jnp.zeros((a * (w + 1) + 1,), bool)
        .at[ins_idx].set(True)[: a * (w + 1)].reshape(a, w + 1)[:, :w]
    )
    ins_d = (
        jnp.zeros((a * (w + 1) + 1,), jnp.int32)
        .at[ins_idx].set(b_dst.reshape(-1))[: a * (w + 1)]
        .reshape(a, w + 1)[:, :w]
    )
    ins_w = (
        jnp.zeros((a * (w + 1) + 1,), jnp.float32)
        .at[ins_idx].set(b_wgt.reshape(-1))[: a * (w + 1)]
        .reshape(a, w + 1)[:, :w]
    )
    ins_lt = jnp.cumsum(is_ins.astype(jnp.int32), axis=1) - is_ins

    # hole compaction: kept lane i lands at kex[i], a left shift bounded
    # by the group delete-run ceiling — (holes+1)-wide select window
    j_row = jnp.arange(w, dtype=jnp.int32)[None, :]
    if holes:
        pad_d = jnp.concatenate(
            [d_keep, jnp.full((a, holes), SENTINEL, jnp.int32)], 1
        )
        pad_w = jnp.concatenate(
            [w_keep, jnp.zeros((a, holes), jnp.float32)], 1
        )
        pad_keep = jnp.concatenate([keep, jnp.zeros((a, holes), bool)], 1)
        pad_kex = jnp.concatenate(
            [kex, jnp.full((a, holes), w + k, jnp.int32)], 1
        )
    else:
        pad_d, pad_w, pad_keep, pad_kex = d_keep, w_keep, keep, kex
    comp_d = jnp.full((a, w), SENTINEL, jnp.int32)
    comp_w = jnp.zeros((a, w), jnp.float32)
    for o in range(holes + 1):
        sel = pad_keep[:, o:o + w] & (pad_kex[:, o:o + w] == j_row)
        comp_d = jnp.where(sel, pad_d[:, o:o + w], comp_d)
        comp_w = jnp.where(sel, pad_w[:, o:o + w], comp_w)

    r = jnp.clip(j_row - ins_lt, 0, w - 1)
    g_d = jnp.take_along_axis(comp_d, r, axis=1)
    g_w = jnp.take_along_axis(comp_w, r, axis=1)
    counts = (n_kept + jnp.sum(new_ins.astype(jnp.int32), axis=1)).astype(
        jnp.int32
    )
    valid = j_row < counts[:, None]
    d_out = jnp.where(valid, jnp.where(is_ins, ins_d, g_d), SENTINEL)
    w_out = jnp.where(valid, jnp.where(is_ins, ins_w, g_w), 0.0)
    return d_out, w_out, counts


def _pallas_fits(width: int, k: int) -> bool:
    return max(int(width), int(k)) <= _kernel.MAX_WIDTH


def merge_rows(
    d_rows, w_rows, degs, b_dst, b_wgt, b_del, *, backend="xla",
    interpret=False, max_holes=None,
):
    """Backend-dispatched row merge (parity-test entry point).

    ``max_holes`` (static) bounds the delete-hole compaction window of
    the XLA formulation; None means the full run width.  The Pallas
    backend merges rows up to the kernel's ``MAX_WIDTH`` (and runs up to
    as many ops); wider ones take the XLA formulation.
    """
    if backend == "pallas" and _pallas_fits(d_rows.shape[1], b_dst.shape[1]):
        return _kernel.merge_rows_pallas(
            d_rows, w_rows, degs, b_dst, b_wgt, b_del, interpret=interpret
        )
    if backend in ("pallas", "xla"):
        return _merge_rows_xla(
            d_rows, w_rows, degs, b_dst, b_wgt, b_del, max_holes=max_holes
        )
    raise ValueError(f"unknown slot_update backend: {backend!r}")


# ---------------------------------------------------------------------------
# fused multi-group apply (+ optional fused walk epilogue) — DESIGN.md §12
# ---------------------------------------------------------------------------
def choose_scatter(cap_e: int, touched: int) -> bool:
    """Write-back dispatch: scatter per group (TPU / huge-arena small
    batch) vs one full-buffer gather rebuild (the off-TPU default)."""
    on_tpu = jax.default_backend() == "tpu"
    return on_tpu or (cap_e > REBUILD_MAX_CAP and touched * 10 < cap_e)


def quantized_prefix(cap_e: int, bump: int) -> int:
    """Bump prefix bound on the cap_e/8 lattice (the walk's edges_hi
    policy): coarse enough that streaming bump growth rarely changes the
    static rebuild shape, tight enough to skip the SENTINEL tail."""
    q = max(cap_e // 8, 128)
    return min(-(-max(int(bump), 1) // q) * q, cap_e)


def host_patch_layout(layout, rows, old_starts, old_caps, new_starts,
                      new_caps, grow, map_hi: int, cap_v: int,
                      has_moves: bool):
    """Host-built rebuild operands for the gather write-back.

    ``layout`` is [(width, gsel, a_pad), ...] in group-iteration order —
    merged group g's rows occupy consecutive [a_pad, width] regions of
    the concatenated patch stream.  ``slot_map[map_hi]`` (``map_hi`` =
    the quantized bump prefix; every touched slot sits below it) holds
    -1 for untouched slots, a patch index for slots of a touched row's
    (possibly new) block, and the trailing SENTINEL slot for vacated old
    blocks.  Shared by the DiGraph arena update and the walk-image patch
    engine (both feed it to ``fused_apply(scatter=False)``).
    """
    patch_base = np.zeros(rows.shape[0], np.int64)
    base = 0
    for wv, gsel, a_pad in layout:
        patch_base[gsel] = base + np.arange(gsel.shape[0], dtype=np.int64) * int(wv)
        base += int(a_pad) * int(wv)
    slot_map = np.full(map_hi, -1, np.int32)
    if has_moves:  # vacated blocks clear via the trailing patch slot
        mv = np.nonzero(grow & (old_starts >= 0) & (old_caps > 0))[0]
        oc = old_caps[mv]
        intra = np.arange(int(oc.sum()), dtype=np.int64) - np.repeat(
            np.cumsum(oc) - oc, oc
        )
        slot_map[np.repeat(old_starts[mv], oc) + intra] = base
    intra = np.arange(int(new_caps.sum()), dtype=np.int64) - np.repeat(
        np.cumsum(new_caps) - new_caps, new_caps
    )
    arena_idx = np.repeat(new_starts, new_caps) + intra
    slot_map[arena_idx] = np.repeat(patch_base, new_caps) + intra
    if has_moves:
        owner_patch = np.full(base + 1, cap_v, np.int32)
        owner_patch[np.repeat(patch_base, new_caps) + intra] = np.repeat(
            rows, new_caps
        )
    else:
        owner_patch = np.zeros(1, np.int32)
    return slot_map, owner_patch


@functools.lru_cache(maxsize=None)
def _jit_fused(groups: tuple, scatter: bool, rebuild_hi: int, any_moves: bool,
               donate: bool, backend: str, interpret: bool, blocks: bool,
               walk: tuple):
    """ONE program for a whole UpdatePlan — and, optionally, the walk.

    ``groups`` is ``((width, a_pad, k, d_k, moves), ...)``: every pow-2
    width class of the plan merges inside the same dispatch (the groups
    touch disjoint rows, so their gathers all read the pre-update buffer
    and their writes never collide).  Compared to one dispatch per group
    this pays a single XLA launch + a single host counts sync per
    *batch* instead of per class.  ``d_k`` bounds each group's
    delete-hole compaction window (see ``_merge_rows_xla``).

    ``blocks`` updates the [lo, hi) interval geometry in-program from
    the merge counts and returns it (the shared-image arena keeps its
    walk operands warm across updates without a host rebuild).  ``walk``
    is ``()`` or ``(steps, nv, edges_hi, nwalks, normalize, engine)``:
    the patched buffers additionally feed the scatter-free interval step
    scan directly, so a steady-state stream round (flush + k-step walk)
    is ONE dispatch with zero intermediate materialization (§12).
    """
    n_g = len(groups)

    # the module stays ``jit_fn``: the benchmark's update-program time
    # reads it by that name
    @jax.named_scope("slot_update.fused_apply")
    def fn(dst, wgt, slot_rows, slot_map, owner_patch, lo, hi, visits0, *ops):
        cap_e = dst.shape[0]
        dst0, wgt0 = dst, wgt
        counts_all = []
        d_patches, w_patches = [], []
        for gi in range(n_g):
            width, a_pad, k, d_k, moves = groups[gi]
            # each group ships 3 packed operands, not 9 loose ones — the
            # per-array jit argument transfer overhead dominates the
            # bytes at these sizes
            row_ops, bdl, bw = ops[gi * 3:(gi + 1) * 3]
            (old_starts, old_caps, new_starts, new_caps, degs,
             row_ids) = (row_ops[i] for i in range(6))
            bd, bl = bdl[0], bdl[1]
            d_rows = util.rows_to_padded(dst0, old_starts, degs, width, SENTINEL)
            w_rows = util.rows_to_padded(wgt0, old_starts, degs, width, 0.0)
            d_rows, w_rows, counts = merge_rows(
                d_rows, w_rows, degs, bd, bw, bl,
                backend=backend, interpret=interpret, max_holes=d_k,
            )
            counts_all.append(counts)
            if blocks or walk:
                # padded rows carry row_ids >= nv and drop out
                lo = lo.at[row_ids].set(new_starts, mode="drop")
                hi = hi.at[row_ids].set(new_starts + counts, mode="drop")
            if scatter:
                lane = jnp.arange(width, dtype=jnp.int32)[None, :]
                if moves:
                    moved = (new_starts != old_starts) & (old_starts >= 0)
                    old_idx = jnp.where(
                        moved[:, None] & (lane < old_caps[:, None]),
                        old_starts[:, None] + lane,
                        cap_e,
                    )
                    dst = dst.at[old_idx.reshape(-1)].set(
                        SENTINEL, mode="drop", unique_indices=True
                    )
                ok = new_starts >= 0
                new_idx = jnp.where(
                    ok[:, None] & (lane < new_caps[:, None]),
                    new_starts[:, None] + lane,
                    cap_e,
                ).reshape(-1)
                dst = dst.at[new_idx].set(
                    d_rows.reshape(-1), mode="drop", unique_indices=True
                )
                wgt = wgt.at[new_idx].set(
                    w_rows.reshape(-1), mode="drop", unique_indices=True
                )
                if moves:
                    slot_rows = slot_rows.at[new_idx].set(
                        jnp.broadcast_to(
                            row_ids[:, None], (a_pad, width)
                        ).reshape(-1),
                        mode="drop",
                        unique_indices=True,
                    )
            else:
                d_patches.append(d_rows)
                w_patches.append(w_rows)
        if not scatter and n_g:
            pd = jnp.concatenate(
                [p.reshape(-1) for p in d_patches]
                + [jnp.full((1,), SENTINEL, jnp.int32)]
            )
            pw = jnp.concatenate(
                [p.reshape(-1) for p in w_patches]
                + [jnp.zeros((1,), jnp.float32)]
            )
            safe = jnp.clip(slot_map, 0, pd.shape[0] - 1)
            touched = slot_map >= 0
            if 0 < rebuild_hi < cap_e:
                # every touched slot sits below the bump pointer: run the
                # gather-select over the (quantized) bump prefix only and
                # splice it back — the SENTINEL tail is never re-read.
                # ``slot_map`` arrives [rebuild_hi]-sized from the host.
                pre_d = jnp.where(
                    touched, pd[safe],
                    jax.lax.dynamic_slice(dst, (0,), (rebuild_hi,)),
                )
                pre_w = jnp.where(
                    touched, pw[safe],
                    jax.lax.dynamic_slice(wgt, (0,), (rebuild_hi,)),
                )
                dst = jax.lax.dynamic_update_slice(dst, pre_d, (0,))
                wgt = jax.lax.dynamic_update_slice(wgt, pre_w, (0,))
                if any_moves:
                    pre_r = jnp.where(
                        touched, owner_patch[safe],
                        jax.lax.dynamic_slice(slot_rows, (0,), (rebuild_hi,)),
                    )
                    slot_rows = jax.lax.dynamic_update_slice(
                        slot_rows, pre_r, (0,)
                    )
            else:
                dst = jnp.where(touched, pd[safe], dst)
                wgt = jnp.where(touched, pw[safe], wgt)
                if any_moves:
                    slot_rows = jnp.where(touched, owner_patch[safe], slot_rows)

        outs = [dst, wgt]
        if any_moves:
            outs.append(slot_rows)
        outs.append(
            jnp.concatenate(counts_all)
            if len(counts_all) > 1
            else counts_all[0]
        )
        if walk:
            from ..slot_walk import ops as _sw  # lazy: avoid import cycle

            steps, nv, edges_hi, nwalks, normalize, engine = walk
            gidx_p = _sw._prep_gidx(dst, nv, edges_hi)
            step = _sw.make_blocked_step(
                gidx_p, lo, hi, nv, engine=engine, interpret=interpret
            )
            v = (
                jnp.asarray(visits0, jnp.float32)
                if nwalks
                else jnp.ones((1, nv), jnp.float32)
            )

            def body(vis, _):
                nxt = step(vis)
                if normalize:
                    nxt = nxt / jnp.maximum(
                        jnp.max(nxt, axis=1, keepdims=True), 1.0
                    )
                return nxt, None

            v, _ = jax.lax.scan(body, v, None, length=steps)
            outs.append(v if nwalks else v[0])
        if blocks or walk:
            outs.extend([lo, hi])
        return tuple(outs)

    if not donate:
        return jax.jit(fn)
    return jax.jit(fn, donate_argnums=(0, 1, 2) if any_moves else (0, 1))


def _fused_apply_ref(dst, wgt, slot_rows, groups, *, any_moves: bool,
                     blocks: bool, wkey: tuple, lo, hi, visits0):
    """Host-numpy fused apply — the fallback chain's floor (DESIGN.md §13).

    Replays the whole plan through ``merge_rows_reference`` with direct
    array writes (the scatter/rebuild distinction collapses on host),
    mirroring the device program's full output contract: patched buffers,
    concatenated counts, refreshed [lo, hi) geometry and — when a walk
    epilogue is fused — the host walk over the patched intervals.  Slow
    by design; its job is stream survival when both device merge
    backends are tripped.
    """
    d = np.array(dst)
    w = np.array(wgt)
    r = np.array(slot_rows) if any_moves else slot_rows
    lo_h = np.array(lo) if (blocks or wkey) else None
    hi_h = np.array(hi) if (blocks or wkey) else None
    counts_all = []
    for width, a_pad, _k, _dk, moves, ops3 in groups:
        row_ops, bdl, bw = ops3
        old_starts, old_caps, new_starts, new_caps, degs, row_ids = (
            np.asarray(row_ops[i], np.int64) for i in range(6)
        )
        d_rows = np.full((a_pad, width), SENTINEL, np.int32)
        w_rows = np.zeros((a_pad, width), np.float32)
        for i in range(a_pad):
            dg = int(degs[i])
            if dg and old_starts[i] >= 0:
                s = int(old_starts[i])
                d_rows[i, :dg] = d[s:s + dg]
                w_rows[i, :dg] = w[s:s + dg]
        out_d, out_w, counts = _refmod.merge_rows_reference(
            d_rows, w_rows, degs, bdl[0], bw, bdl[1]
        )
        counts_all.append(counts.astype(np.int32))
        for i in range(a_pad):
            ns, nc = int(new_starts[i]), int(new_caps[i])
            if ns < 0 or nc <= 0:
                continue  # pad row
            if moves and old_starts[i] >= 0 and old_starts[i] != ns:
                os_, oc = int(old_starts[i]), int(old_caps[i])
                d[os_:os_ + oc] = SENTINEL  # vacated block goes dead
                w[os_:os_ + oc] = 0.0
            d[ns:ns + nc] = out_d[i, :nc]
            w[ns:ns + nc] = out_w[i, :nc]
            if any_moves:
                r[ns:ns + nc] = row_ids[i]
            if lo_h is not None and row_ids[i] < lo_h.shape[0]:
                lo_h[row_ids[i]] = ns
                hi_h[row_ids[i]] = ns + int(counts[i])
    outs = [jnp.asarray(d), jnp.asarray(w)]
    if any_moves:
        outs.append(jnp.asarray(r))
    outs.append(np.concatenate(counts_all) if counts_all else np.zeros(0, np.int32))
    if wkey:
        from ..slot_walk import ref as _sw_ref  # lazy: avoid import cycle

        steps, nv, edges_hi, nwalks, normalize, _engine = wkey
        v0 = (
            np.asarray(visits0, np.float32)
            if nwalks
            else np.ones((1, nv), np.float32)
        )
        v = _sw_ref.slot_walk_host(
            d, None, steps, nv, edges_hi=edges_hi,
            block_lo=lo_h[:nv], block_hi=hi_h[:nv],
            normalize=normalize, visits0=v0,
        )
        outs.append(v if nwalks else v[0])
    if blocks or wkey:
        outs.extend([jnp.asarray(lo_h), jnp.asarray(hi_h)])
    return tuple(outs)


def fused_apply(
    dst, wgt, slot_rows, groups,
    *, scatter: bool, backend: str = "auto", interpret: bool = False,
    donate: bool = True, slot_map=None, owner_patch=None, rebuild_hi: int = 0,
    walk=None, lo=None, hi=None, visits0=None,
):
    """Apply EVERY width group of a plan in one dispatch (DESIGN.md §12).

    ``groups`` is ``[(width, a_pad, k, d_k, moves, operands), ...]``
    with ``operands`` the packed 3-tuple ``(row_ops [6, A] int32 =
    old_starts/old_caps/new_starts/new_caps/degs/row_ids, b_dstdel
    [2, A, K] int32, b_wgt [A, K] f32)`` (numpy fine — jit's argument
    path transfers them; packing matters because per-array transfer
    overhead dominates at these sizes) and ``d_k`` the group's (pow-2)
    delete-run ceiling, bounding the merge's hole-compaction window.
    ``scatter=False`` takes the host-mapped gather rebuild
    (``host_patch_layout`` supplies ``slot_map``/``owner_patch``);
    ``rebuild_hi`` (static, quantized to the caller's bump lattice)
    bounds that pass to the allocated prefix so the SENTINEL tail is
    never re-read.  ``walk=(steps, nv, edges_hi, nwalks, normalize,
    engine)`` fuses the k-step interval walk into the same program, fed
    by the in-program-updated [lo, hi) geometry; passing ``lo``/``hi``
    WITHOUT ``walk`` still updates and returns them (interval-cache
    refresh for the shared arena image).

    Returns ``(dst, wgt, slot_rows, counts_list, extra)`` where
    ``extra`` is ``None``, ``(lo2, hi2)`` (blocks-only), or
    ``(visits, lo2, hi2)`` (fused walk).
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"unknown slot_update backend: {backend!r}")
    gkey = tuple(
        (int(w), int(a), int(k), int(dk), bool(mv))
        for w, a, k, dk, mv, _ in groups
    )
    any_moves = any(g[4] for g in gkey)
    blocks = walk is None and lo is not None and hi is not None
    wkey = () if walk is None else tuple(walk)
    ops_flat = [o for *_hdr, ops9 in groups for o in ops9]
    dummy = np.zeros(1, np.int32)

    # dispatch runs through the health-gated fallback chain (DESIGN.md
    # §13).  Injected faults and compile/lowering failures fire BEFORE
    # execution, so operands are intact for the next link; only the
    # first attempt may donate — a retry must still own its inputs.  (A
    # real device failure AFTER a donated buffer was consumed is not
    # retryable: jax reports the deleted buffer and the chain exhausts.)
    state = {"first": True}

    def _dispatch(b: str):
        first, state["first"] = state["first"], False
        if b == "ref":
            return _fused_apply_ref(
                dst, wgt, slot_rows, groups, any_moves=any_moves,
                blocks=blocks, wkey=wkey, lo=lo, hi=hi, visits0=visits0,
            )
        # a walk engine tied to the failing backend degrades with it; an
        # explicitly mixed request (e.g. xla merge + pallas walk parity
        # runs) keeps its engine
        wk = wkey[:5] + (b,) if (wkey and wkey[5] == backend) else wkey
        fn = _jit_fused(
            gkey, bool(scatter), int(rebuild_hi), any_moves,
            donate and first, b, interpret, blocks, wk,
        )
        return fn(
            dst, wgt, slot_rows,
            dummy if slot_map is None else slot_map,
            dummy if owner_patch is None else owner_patch,
            dummy if lo is None else lo,
            dummy if hi is None else hi,
            np.zeros((1, 1), np.float32) if visits0 is None else visits0,
            *ops_flat,
        )

    with jax.profiler.TraceAnnotation("slot_update.dispatch"):
        out, used = _fb.run_chain("slot_update", backend, _dispatch)
        STATS["dispatches"] += 1
        if used == "pallas":
            STATS["wide_groups"] += sum(
                not _pallas_fits(w, k) for w, _a, k, _dk, _mv in gkey
            )
        i = 2
        if any_moves:
            new_rows = out[i]
            i += 1
        else:
            new_rows = slot_rows
        # one concatenated counts sync, split back per group on host
        counts_cat = np.asarray(out[i])
        i += 1
    counts, at = [], 0
    for _w, a_pad, *_r in gkey:
        counts.append(counts_cat[at:at + a_pad])
        at += a_pad
    if walk is not None:
        extra = tuple(out[i:i + 3])
    elif blocks:
        extra = tuple(out[i:i + 2])
    else:
        extra = None
    return out[0], out[1], new_rows, counts, extra
