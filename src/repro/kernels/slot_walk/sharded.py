"""Sharded walk-image traversal (DESIGN.md §14).

The dense WalkImage shards by tile range: each device owns a contiguous
vertex block's packed tiles and runs the SAME scatter-free blocked step
(``ops.make_blocked_step``) the single-device engine uses — intra-tile
cumsum, TwoSum-compensated inter-tile scan, ``P[hi] - P[lo]`` interval
reads.  Shard cuts align to block boundaries by construction (a vertex's
block lives wholly inside its owner's slot space), so the inter-tile
base scan CANCELS within each shard and never crosses devices.  The only
cross-shard exchange per walk step is the frontier: every shard emits
its own ``[B, rows_max]`` visits slice and an ``all_gather`` reassembles
the ``[B, V_pad]`` frontier — (S-1)·rows_max·4 ≈ |V|·4 bytes received
per device per step, independent of |E|.

Two bit-identical builders share the math:

  * ``make_sharded_walk`` — the shard_map program over a 1-D ``("data",)``
    mesh (one jitted dispatch for the whole k-step walk);
  * ``make_local_walk``   — the same per-shard step closures looped on one
    device (meshless parity tests and the S=1 degenerate row).

``collective_bytes_per_step`` proves the model by traversing the lowered
jaxpr: the per-device bytes every collective receives, scan trip counts
folded in — no runtime tracing hooks, the program IS the evidence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr as _ClosedJaxpr, Jaxpr as _Jaxpr
from jax.sharding import NamedSharding, PartitionSpec as P

from . import ops as _ops


def _shard_step(dst_l, lo_l, hi_l, v_pad: int, e_hi: int):
    """This shard's blocked step: [B, v_pad] frontier -> [B, v_pad] visits.

    Rows outside the shard's owned range carry lo == hi == 0, so their
    output is exactly 0.0 and the owner's slice is the only information
    the step produces — the frontier exchange below carries it.
    """
    gidx_p = _ops._prep_gidx(dst_l, v_pad, e_hi)
    return _ops.make_blocked_step(gidx_p, lo_l, hi_l, v_pad)


@functools.lru_cache(maxsize=None)
def make_sharded_walk(
    mesh, steps: int, n_shards: int, rows_max: int, cap_e: int, e_hi: int,
    nwalks: int,
):
    """jitted shard_map walk: (dst [S,cap_e], lo/hi [S,v_pad], vis [B,v_pad]).

    One device program for the whole k-step walk; per step each shard
    computes its own visits slice and ``all_gather``s the frontier
    (tiled, so the output IS the next [B, v_pad] frontier).  The result
    is replicated — ``check_vma=False`` because jax cannot prove an
    all_gather'ed value replicated across the unrolled scan.
    """
    v_pad = n_shards * rows_max

    def shard_fn(dst_g, lo_g, hi_g, vis):
        step = _shard_step(dst_g[0], lo_g[0], hi_g[0], v_pad, e_hi)
        idx = jax.lax.axis_index("data")

        def one(v, _):
            own = jax.lax.dynamic_slice_in_dim(
                step(v), idx * rows_max, rows_max, axis=1
            )
            return jax.lax.all_gather(own, "data", axis=1, tiled=True), None

        vis, _ = jax.lax.scan(one, vis, None, length=steps)
        return vis

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("data", None), P("data", None), P("data", None), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def make_local_walk(
    steps: int, n_shards: int, rows_max: int, cap_e: int, e_hi: int,
    nwalks: int,
):
    """Single-device emulation of the sharded walk, same math shard-by-shard.

    Each shard's step closure runs on its own tile range and contributes
    exactly its owned visits slice; the concat stands in for the
    all_gather.  Exists so parity tests need no mesh and the bench's
    shards=1 row is a real program, not a special case.
    """
    v_pad = n_shards * rows_max

    @jax.jit
    def walk(dst_g, lo_g, hi_g, vis):
        steps_fns = [
            _shard_step(dst_g[s], lo_g[s], hi_g[s], v_pad, e_hi)
            for s in range(n_shards)
        ]

        def one(v, _):
            parts = [
                jax.lax.dynamic_slice_in_dim(
                    f(v), s * rows_max, rows_max, axis=1
                )
                for s, f in enumerate(steps_fns)
            ]
            return jnp.concatenate(parts, axis=1), None

        vis, _ = jax.lax.scan(one, vis, None, length=steps)
        return vis

    return walk


# ---------------------------------------------------------------------------
# collective-bytes model proof (DESIGN.md §14)
# ---------------------------------------------------------------------------
_RECV_COLLECTIVES = ("all_gather", "all_gather_invariant")
_MOVE_COLLECTIVES = ("ppermute", "all_to_all", "pgather")


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _sub_jaxprs(params):
    for v in params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, _ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, _Jaxpr):
                yield x


def _collective_bytes(jaxpr, mult: int = 1) -> int:
    """Per-device bytes received by collectives under ``jaxpr``.

    ``all_gather`` receives (out - in) bytes per device (its own shard it
    already holds); data-movement collectives count their full output.
    Scan bodies multiply by trip count; every other sub-jaxpr (pjit,
    shard_map, cond branches) recurses at the current multiplier — the
    shard_map body's avals are per-shard shapes, which is exactly the
    per-device accounting the |V|·4 model is stated in.
    """
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        m = mult * int(eqn.params["length"]) if name == "scan" else mult
        if name in _RECV_COLLECTIVES:
            out_b = sum(_aval_bytes(v) for v in eqn.outvars)
            in_b = sum(_aval_bytes(v) for v in eqn.invars)
            total += m * max(out_b - in_b, 0)
        elif name in _MOVE_COLLECTIVES:
            total += m * sum(_aval_bytes(v) for v in eqn.outvars)
        for sub in _sub_jaxprs(eqn.params):
            total += _collective_bytes(sub, m)
    return total


def collective_bytes_per_step(
    mesh, steps: int, n_shards: int, rows_max: int, cap_e: int, e_hi: int,
    nwalks: int,
) -> int:
    """Measured per-device collective bytes per walk step, via the jaxpr.

    Builds the exact walk program ``make_sharded_walk`` dispatches and
    inspects its lowered form — the proof field bench rows publish
    against the ``(S-1)·rows_max·B·4`` frontier model.  S=1 programs
    still contain the all_gather; its out == in, so the count is 0.
    """
    v_pad = n_shards * rows_max
    b = max(nwalks, 1)
    args = (
        jax.ShapeDtypeStruct((n_shards, cap_e), jnp.int32),
        jax.ShapeDtypeStruct((n_shards, v_pad), jnp.int32),
        jax.ShapeDtypeStruct((n_shards, v_pad), jnp.int32),
        jax.ShapeDtypeStruct((b, v_pad), jnp.float32),
    )
    fn = make_sharded_walk(mesh, steps, n_shards, rows_max, cap_e, e_hi, nwalks)
    jaxpr = jax.make_jaxpr(fn)(*args)
    return _collective_bytes(jaxpr.jaxpr) // max(steps, 1)


def model_bytes_per_step(n_shards: int, rows_max: int, nwalks: int) -> int:
    """The |V|·4 frontier model: bytes each device receives per step."""
    return (n_shards - 1) * rows_max * max(nwalks, 1) * 4
