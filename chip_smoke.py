"""Chip smoke run: the served graph store's main path on a TPU.

Builds a Graph500 Kronecker graph (the ``web`` family: A/B/C =
0.57/0.19/0.19, edge factor 16, symmetric, weighted) from ``--seed`` as
a ``DiGraph``, wraps it in a ``DurableGraph`` with its WAL on and serves
it with ``WalkServer``.  Walk requests (4 steps, 4 seeds each) go out in
groups, with an insert and a delete batch of 1e-3·|E| edges and three
256-edge batches between the groups.  Every served walk is checked
against the per-generation numpy oracle.  The run fails on any torn,
lost or failed request, on any fall-through of the kernel fallback
chain, and when the walk or the update merge did not run on the Pallas
kernels.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # ShardedGraph over a 4-chip mesh only

The scale starts at 22 (4.2M vertices, ~1.3e8 edges) and the server's
``batch_max`` at 16; both come down only as far as the compiled walk
program's ``memory_analysis`` requires, the scale never below 20.
Scale 22 is also where the four-chip run starts: the id-block partition
puts ~55% of a Graph500 graph's slots on shard 0, and a ``v5e:2x2``
compile of the scale-23 sharded walk needs ~24 GB per device.  Updates
wait for the walks before them, and walks for the update before them,
so at most two arena generations and one walk program share the device.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU it exits non-zero and names the platform JAX found.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))

STEPS = 4
SEEDS_PER_WALK = 4
WALKS_PER_GROUP = 4
#: the paper's update-batch fraction of |E| (benchmarks/common.py)
BATCH_FRACTION = 1e-3
SMALL_BATCH = 256
SCALE = 22
MIN_SCALE = 20
BATCH_CHOICES = (16, 8, 4)
#: device bytes left free beside the walk program and the arena copies
MARGIN_BYTES = 1 << 30
WAIT_S = 900.0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Phases:
    """Wall time per phase, printed as each one ends."""

    def __init__(self):
        self.t = time.perf_counter()

    def done(self, name: str) -> float:
        now = time.perf_counter()
        dt, self.t = now - self.t, now
        log(f"phase {name}: {dt:.2f}s")
        return dt


def program_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )


def fit_batch(lower, resident: int, limit: int):
    """Largest batch in BATCH_CHOICES whose walk program fits beside
    ``resident`` bytes: ``(batch, compiled, need_bytes, compile_s)`` or
    None.  ``lower(b)`` lowers the walk program for a [b, V] batch.  A
    program the compiler itself cannot place in device memory does not
    fit either."""
    import jax

    for b in BATCH_CHOICES:
        t0 = time.perf_counter()
        try:
            compiled = lower(b).compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            log(f"walk program B={b}: the compiler finds it does not fit "
                f"({str(e).splitlines()[0]}; {time.perf_counter() - t0:.2f}s)")
            continue
        dt = time.perf_counter() - t0
        need = program_bytes(compiled) + resident
        log(f"walk program B={b}: {program_bytes(compiled) / 1e9:.3f} GB "
            f"+ resident {resident / 1e9:.3f} GB = {need / 1e9:.3f} GB of "
            f"{limit / 1e9:.3f} GB (compile {dt:.2f}s)")
        if need <= limit:
            return b, compiled, need, dt
    return None


def next_quantum(edges_hi: int, cap_e: int) -> int:
    """The walk bound one step up the cap_e/8 lattice: inserts move the
    bump pointer, so the program is sized for the next bound too."""
    return min(edges_hi + max(cap_e // 8, 128), cap_e)


def update_plans(rng, csr, nv: int):
    from repro.core import edgebatch, updates

    def batch(n_ins: int, n_del: int):
        return updates.plan_update(
            inserts=edgebatch.random_insertions(
                rng, nv, n_ins, weighted_range=(0.5, 1.5)
            ) if n_ins else None,
            deletes=edgebatch.random_deletions(rng, csr, n_del)
            if n_del else None,
        )

    big = max(int(round(int(csr.m) * BATCH_FRACTION)), 1)
    half = SMALL_BATCH // 2
    return [
        (f"insert {big}", batch(big, 0)), (f"delete {big}", batch(0, big)),
        (f"insert {SMALL_BATCH}", batch(SMALL_BATCH, 0)),
        (f"delete {SMALL_BATCH}", batch(0, SMALL_BATCH)),
        (f"mixed {SMALL_BATCH}", batch(half, half)),
    ]


def serve_and_verify(rep, csr, *, batch_max: int, seed: int, phases: Phases):
    """Serve walk groups with update batches between them; verify every
    walk against the oracle.  Returns (stats, failures)."""
    import numpy as np

    from repro.launch import serve as launch_serve
    from repro.runtime import durable
    from repro.runtime import serve as serve_mod

    nv = int(csr.n)
    rng = np.random.default_rng(seed + 1)
    plans = update_plans(rng, csr, nv)
    failures = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        dg = durable.DurableGraph(
            rep, os.path.join(tmp, "wal"), os.path.join(tmp, "ckpt"),
            fsync=True,
        )
        phases.done("durable graph (WAL + step-0 checkpoint)")
        oracle = launch_serve.GenerationOracle(csr)
        phases.done("oracle build")
        server = serve_mod.WalkServer(dg, batch_max=batch_max).start()
        walks, upds = [], []
        for i in range(len(plans) + 1):
            group = [
                server.submit_walk(
                    rng.integers(0, nv, SEEDS_PER_WALK), steps=STEPS
                )
                for _ in range(WALKS_PER_GROUP)
            ]
            for t in group:
                if not t.wait(WAIT_S):
                    failures.append("walk ticket still pending")
            walks.extend(group)
            phases.done(f"walk group {i} ({len(group)} walks)")
            if i < len(plans):
                name, plan = plans[i]
                t = server.submit_update(plan)
                if not t.wait(WAIT_S):
                    failures.append(f"update {name} still pending")
                upds.append((t, plan))
                phases.done(f"update {name} (ops={plan.n_ops}, "
                            f"status={t.status}, dm={t.dm})")
        stats = server.stop()
        server.assert_no_lost()
        dg.close()
    phases.done("server stop")
    torn, checked = launch_serve.count_torn_reads(
        oracle, walks, upds, sample=1.0
    )
    phases.done(f"oracle check of {checked} walks")
    lost = sum(t.status == serve_mod.PENDING for t in walks) + sum(
        t.status == serve_mod.PENDING for t, _ in upds
    )
    not_served = [t.status for t in walks if t.status != serve_mod.SERVED]
    not_acked = [t.status for t, _ in upds if t.status != serve_mod.SERVED]
    log(f"served {stats['served']}/{stats['submitted']} walks in "
        f"{stats['batches']} batches (max {stats['max_batch']}), "
        f"updates applied {stats['updates_applied']}, "
        f"generations {stats['generation'] + 1}, torn={torn}/{checked}, "
        f"lost={lost}, failed={stats['failed']}")
    if torn:
        failures.append(f"torn reads: {torn} of {checked}")
    if checked != len(walks):
        failures.append(f"checked {checked} of {len(walks)} walks")
    if lost:
        failures.append(f"lost requests: {lost}")
    if stats["failed"] or stats["updates_failed"]:
        failures.append(f"failed: walks {stats['failed']}, "
                        f"updates {stats['updates_failed']}")
    if not_served or not_acked:
        failures.append(f"walks not served: {not_served}, updates not "
                        f"acked: {not_acked}")
    return stats, failures


def check_backends(expect_walk) -> list:
    """Zero fall-throughs, and the Pallas kernels served both sites."""
    from repro.kernels import fallback
    from repro.kernels.slot_update import ops as su_ops

    falls = dict(fallback.BREAKER.fallthroughs)
    log(f"fallback fall-throughs {falls or 0}, last used "
        f"{dict(fallback.LAST_USED)}, merge groups wider than the Pallas "
        f"kernel (XLA merge in the same program): {su_ops.STATS['wide_groups']}")
    failures = []
    if sum(falls.values()):
        failures.append(f"fallback chain fell through: {falls}")
    sites = ("slot_walk", "slot_update") if expect_walk else ("slot_update",)
    for site in sites:
        if fallback.LAST_USED.get(site) != "pallas":
            failures.append(
                f"{site} served from {fallback.LAST_USED.get(site)!r}, "
                "not pallas"
            )
    return failures


def fit_scale(args, limit: int, phases: Phases, build):
    """Largest scale from SCALE down to MIN_SCALE, and batch,
    whose walk program fits: ``(scale, csr, rep, fit)`` or None.

    ``build(csr)`` returns ``(rep, cap_e, lower)`` for the graph, where
    ``lower(b)`` lowers its walk program for a [b, V] batch.
    """
    from repro.io import synthetic

    for scale in range(SCALE, MIN_SCALE - 1, -1):
        csr = synthetic.make_graph(
            "web", scale=scale, edge_factor=16, seed=args.seed, weighted=True
        )
        phases.done(f"generate scale {scale}")
        rep, cap_e, lower = build(csr)
        phases.done(f"{type(rep).__name__} build")
        # beside the walk program (which counts dst): this generation's
        # wgt/slot_rows and one copy-on-write set of dst/wgt/slot_rows
        fit = fit_batch(lower, 5 * cap_e * 4 + MARGIN_BYTES, limit)
        phases.done("walk program fit")
        if fit is not None:
            b, _compiled, need, dt = fit
            log(f"scale {scale}, batch_max {b}: the largest batch in "
                f"{BATCH_CHOICES} whose walk program fits, {need / 1e9:.3f} "
                f"GB of {limit / 1e9:.3f} GB per device; walk compile "
                f"{dt:.2f}s")
            return scale, csr, rep, fit
        log(f"scale {scale}: no batch_max >= {BATCH_CHOICES[-1]} fits; "
            "lowering the scale")
        del rep, csr, lower
    return None


def one_chip(args, limit: int, phases: Phases) -> list:
    import jax
    import jax.numpy as jnp

    from repro.core import DiGraph
    from repro.kernels.slot_walk import ops as sw_ops

    def build(csr):
        rep = DiGraph.from_csr(csr)
        rep.block_on()
        img = rep.to_walk_image()
        nv, cap_e = int(img.nv), int(img.cap_e)
        e_hi = next_quantum(img.edges_hi(), cap_e)
        log(f"|V|={nv} |E|={int(csr.m)} cap_e={cap_e} "
            f"edges_hi={img.edges_hi()}, walk program sized for {e_hi}")
        sds = jax.ShapeDtypeStruct

        def lower(b):
            return sw_ops.slot_walk_multi_blocked.lower(
                sds((cap_e,), jnp.int32), sds((cap_e,), jnp.int32),
                sds((nv,), jnp.int32), sds((nv,), jnp.int32),
                sds((b, nv), jnp.float32),
                STEPS, nv, edges_hi=e_hi, engine="pallas",
            )

        return rep, cap_e, lower

    picked = fit_scale(args, limit, phases, build)
    if picked is None:
        return [f"no scale >= {MIN_SCALE} fits the device"]
    _scale, csr, rep, (b, _compiled, _need, _dt) = picked
    _stats, failures = serve_and_verify(
        rep, csr, batch_max=b, seed=args.seed, phases=phases
    )
    return failures + check_backends(expect_walk=True)


def four_chips(args, limit: int, phases: Phases) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import distributed as dist
    from repro.kernels.slot_walk import sharded
    from repro.launch.mesh import host_mesh

    mesh = host_mesh(4)
    vis = NamedSharding(mesh, P(None, None))

    def build(csr):
        rep = dist.shard_csr(csr, 4, mesh=mesh)
        rep.block_on()
        cap_e, v_pad = rep.cap_e, rep.v_pad
        e_hi = next_quantum(rep.edges_hi(), cap_e)
        log(f"|V|={int(csr.n)} |E|={int(csr.m)} 4 shards, cap_e={cap_e} "
            f"per shard, edges_hi={rep.edges_hi()}, walk program sized for "
            f"{e_hi}")
        dst_g, lo_g, hi_g = rep._assemble()

        def lower(b):
            fn = sharded.make_sharded_walk(
                mesh, STEPS, 4, rep.rows_max, cap_e, e_hi, b
            )
            return fn.lower(
                dst_g, lo_g, hi_g,
                jax.ShapeDtypeStruct((b, v_pad), jnp.float32, sharding=vis),
            )

        return rep, cap_e, lower

    picked = fit_scale(args, limit, phases, build)
    if picked is None:
        return [f"no scale >= {MIN_SCALE} fits four devices"]
    _scale, csr, rep, (b, compiled, _need, _dt) = picked
    failures = []
    for name in ("dst", "wgt", "rows"):
        devs = {d for img in rep.shards for d in getattr(img, name).devices()}
        log(f"shard {name} devices: {sorted(d.id for d in devs)}")
        if len(devs) != 4:
            failures.append(f"shard {name} on {len(devs)} devices, not 4")
    has_gather = "all-gather" in compiled.as_text()
    log(f"frontier all-gather in the compiled sharded walk: {has_gather}")
    if not has_gather:
        failures.append("compiled sharded walk has no all-gather")
    _stats, more = serve_and_verify(
        rep, csr, batch_max=b, seed=args.seed, phases=phases
    )
    return failures + more + check_backends(expect_walk=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r}",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    dev = devices[0]
    stats = dev.memory_stats() or {}
    limit = int(stats.get("bytes_limit", 0))
    if not limit:
        print("chip_smoke: the device reports no bytes_limit", file=sys.stderr)
        return 1
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"bytes_limit {limit}, compile cache {cache}")
    phases = Phases()
    if args.chips == 4:
        failures = four_chips(args, limit, phases)
    else:
        failures = one_chip(args, limit, phases)
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devices[: args.chips]]
    log(f"peak_bytes_in_use {peaks}")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
