"""The comparison that decides ``correct``.

Each number below is compared with its limit from the configuration's
``correct_limits``; the run is correct when every one is within it.

- ``walk_err``: over the checked walks, the largest
  ``max |served - reference| / max |reference|`` over a walk's vertices,
  the reference walking the generation the answer names (the device
  walk program and snapshot isolation: walks one update batch apart
  differ by ~1e-3 of their largest count);
- ``walks_checked``: how many served walks were compared (at least the
  limit);
- ``stale_walks``: walks answered at a generation older than one an ack
  had already named when the walk was sent (an acked update must be
  visible from its generation on);
- ``ack_order``: acks whose generation is lower than that of an update
  submitted before them (the writer applies in submission order);
- ``edges_wrong``: edges of the final graph missing, extra, or with
  another weight than the reference's (the writer, the fused update
  program and the seal);
- ``wal_wrong``: acked batches whose write-ahead-log record is missing,
  out of order or different, and records with a bad CRC (durability);
- ``lost``: requests whose answer never came: not served, not acked and
  not refused (a refusal, such as the queue still full when the server
  stops after the window, is not a wrong answer).
"""
from __future__ import annotations

import numpy as np

from chipbench import reference

#: walks walked together by the reference
CHUNK = 32


def _count_key_diff(a_keys, a_w, b_keys, b_w) -> int:
    if a_keys.shape == b_keys.shape and np.array_equal(a_keys, b_keys):
        return int(np.count_nonzero(a_w != b_w))
    common, ia, ib = np.intersect1d(a_keys, b_keys, assume_unique=True,
                                    return_indices=True)
    diff = a_keys.shape[0] + b_keys.shape[0] - 2 * common.shape[0]
    return int(diff + np.count_nonzero(a_w[ia] != b_w[ib]))


def _wal_wrong(acked, wal) -> int:
    records, bad = wal
    wrong = bad + abs(len(records) - len(acked))
    for rec, upd in zip(records, acked):
        keys, wgt, dele = reference.canonical_ops(*upd.ins, *upd.dels)
        _seq, src, dst, w, d = rec
        same = (
            src.shape[0] == keys.shape[0]
            and np.array_equal(reference.keys_of(src, dst), keys)
            and np.array_equal(w, wgt) and np.array_equal(d, dele)
        )
        wrong += not same
    return wrong


def compare(base, traffic, got_keys, got_wgt, wal, steps: int,
            limits: dict, *, want_bytes: bool = False) -> dict:
    """Compare one run with the reference.  ``base`` is the generated
    graph, ``traffic`` the clients' logs.  Returns ``correct``, the
    ``checks`` and, with ``want_bytes``, ``walk_bytes``: the least bytes
    every walk of the window needs, summed."""
    log = traffic.log
    updates = sorted(traffic.warm.updates + log.updates, key=lambda u: u.order)
    acked = [u for u in updates if u.status == "served"]
    ack_order = sum(
        b.generation < a.generation for a, b in zip(acked, acked[1:])
    )
    lost = sum(r.status == "pending" for r in log.walks + log.updates)
    served = [w for w in log.walks if w.status == "served"]
    stale = sum(w.generation < w.acked_gen_at_send for w in served)
    check_max = int(traffic.mix.get("check_max", 1 << 30))
    checked = [w for w in served if w.visits is not None][:check_max]
    checked_ids = {id(w) for w in checked}
    walked = served if want_bytes else checked

    state = reference.EdgeState(base.offsets, base.dst, base.wgt, base.n)
    walker = reference.Walker(state) if walked else None
    err = 0.0
    total_bytes = 0
    pending = list(acked)
    by_gen: dict = {}
    for w in walked:
        by_gen.setdefault(w.generation, []).append(w)
    for g in sorted(by_gen):
        while pending and pending[0].generation <= g:
            u = pending.pop(0)
            state.apply(*u.ins, *u.dels)
        delta = state.delta()
        group = by_gen[g]
        for i in range(0, len(group), CHUNK):
            chunk = group[i:i + CHUNK]
            seeds = [w.seeds for w in chunk]
            weights = [w.weights for w in chunk]
            vis, nbytes = walker.walk(delta, seeds, steps, weights_list=weights)
            total_bytes += int(nbytes.sum())
            for j, w in enumerate(chunk):
                if id(w) in checked_ids:
                    err = max(err, reference.walk_error(w.visits, vis.column(j)))
    for u in pending:
        state.apply(*u.ins, *u.dels)
    want_keys, want_wgt = state.edges()
    numbers = {
        "walk_err": (err, limits["walk_err"], "max"),
        "walks_checked": (len(checked), limits["walks_checked"], "min"),
        "stale_walks": (stale, 0, "max"),
        "ack_order": (ack_order, 0, "max"),
        "edges_wrong": (
            _count_key_diff(got_keys, got_wgt, want_keys, want_wgt), 0, "max"
        ),
        "wal_wrong": (_wal_wrong(acked, wal), 0, "max"),
        "lost": (lost, 0, "max"),
    }
    checks, correct = {}, True
    for name, (value, limit, kind) in numbers.items():
        ok = value <= limit if kind == "max" else value >= limit
        correct &= bool(ok)
        checks[name] = {"value": value, "limit": limit}
    return {
        "correct": correct, "checks": checks,
        "walk_bytes": total_bytes if want_bytes else None,
    }
