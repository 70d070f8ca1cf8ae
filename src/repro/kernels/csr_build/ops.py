"""COO → CSR → arena construction engines (DESIGN.md §10).

The seed built CSRs with a host ``np.lexsort`` — O(M log M) with two key
passes and the slowest single step of graph loading.  This package keeps
the paper's Alg 5 structure (partitioned degree count + shifted-offset
fill) and realizes it as a counting-sort build with three engines:

  host    pack (src, dst) into ONE int64 key and radix argsort it
          (``np.argsort(kind="stable")`` is a radix sort for ints — on
          this container 53k edges sort in ~1ms vs ~5ms for the seed
          lexsort).  Degrees come from a partitioned bincount, offsets
          from one cumsum, and the sorted order IS the shifted-offset
          fill.  Default off-TPU: measured faster than dispatching XLA
          programs for every bench graph size.
  xla     the same counting sort as one jitted program: a multi-operand
          ``lax.sort`` keyed on (src, dst) — no id-width packing limit —
          plus scatter-add degrees and cumsum offsets, all fused.
          Default on TPU, where the host round-trip is the cost.
  pallas  the xla fill with the degree histogram computed by the
          partitioned tile kernel in ``kernel.py`` (TPU; ``interpret=``
          for parity tests elsewhere).

``arena_image`` builds the DiGraph slotted-arena payload (dst/wgt/
slot_rows) straight from CSR arrays with a numpy fill and one transfer,
so load never materializes an intermediate python-object graph.  ``pages_image`` is the same fill quantized to
ChunkedGraph's PAGE-sized chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import util
from . import kernel as _kernel

SENTINEL = util.SENTINEL
EB = _kernel.EB


def default_engine() -> str:
    return "xla" if jax.default_backend() == "tpu" else "host"


# ---------------------------------------------------------------------------
# degree counting (paper Alg 5 lines 4-8)
# ---------------------------------------------------------------------------
def count_degrees(src, n: int, *, num_partitions: int = 4,
                  engine: str = "auto", interpret: bool = False):
    """Per-vertex degree histogram; out-of-range sources are dropped.

    ``num_partitions`` keeps the paper's per-partition counting shape on
    the host engine (partial bincounts summed — the shard layout of the
    distributed builder); the device engines express the same partition
    structure as edge tiles.
    """
    if engine == "auto":
        engine = default_engine()
    if engine == "host":
        s = np.asarray(src, np.int64)
        s = s[(s >= 0) & (s < n)]
        rho = max(int(num_partitions), 1)
        bounds = np.linspace(0, s.shape[0], rho + 1).astype(np.int64)
        deg = np.zeros(n, np.int64)
        for p in range(rho):
            deg += np.bincount(s[bounds[p]:bounds[p + 1]], minlength=n)
        return deg
    if engine == "xla":
        return _jit_count(int(n))(jnp.asarray(src))
    if engine == "pallas":
        nv = -(-int(n) // EB) * EB
        s = np.asarray(src, np.int64)
        m_pad = -(-max(s.shape[0], 1) // EB) * EB
        tiles = np.full(m_pad, nv, np.int32)
        tiles[: s.shape[0]] = np.where((s >= 0) & (s < n), s, nv)
        deg = _kernel.count_degrees_pallas(
            jnp.asarray(tiles.reshape(-1, EB)), nv=nv, interpret=interpret
        )
        return deg[:n]
    raise ValueError(f"unknown csr_build engine: {engine!r}")


@functools.lru_cache(maxsize=None)
def _jit_count(n: int):
    def fn(src):
        ok = (src >= 0) & (src < n)
        return jnp.zeros((n,), jnp.int32).at[
            jnp.where(ok, src, n)
        ].add(1, mode="drop")

    return jax.jit(fn)


# ---------------------------------------------------------------------------
# the counting-sort CSR fill
# ---------------------------------------------------------------------------
def is_coo_sorted(src: np.ndarray, dst: np.ndarray) -> bool:
    """True when edges are already in (src, dst) order (CSR-order file)."""
    if src.shape[0] < 2:
        return True
    key = (src.astype(np.int64) << 32) | dst.astype(np.uint32).astype(np.int64)
    return bool((key[1:] >= key[:-1]).all())


def sort_coo_host(src: np.ndarray, dst: np.ndarray, *values: np.ndarray):
    """Stable (src, dst) order via ONE packed-key radix argsort.

    Packing both int32 ids into an int64 key turns the seed's two-pass
    ``np.lexsort`` into a single radix sort — the core host-side speedup
    of the ingest engine.  Stability preserves file order among duplicate
    keys (the dedup-keep-first contract).
    """
    key = (src.astype(np.int64) << 32) | dst.astype(np.uint32).astype(np.int64)
    order = np.argsort(key, kind="stable")
    return (src[order], dst[order], *(v[order] for v in values))


@functools.lru_cache(maxsize=None)
def _jit_coo_to_csr(n: int, m: int):
    """Fused device counting sort: lex sort + degree scatter + cumsum.

    Pad edges must carry src >= n; they sort to the tail and fall out of
    the degree histogram, so offsets/dst/wgt prefixes match the host
    engine bit for bit.
    """

    def fn(src, dst, wgt):
        src, dst, wgt = jax.lax.sort(
            (src, dst, wgt), dimension=0, num_keys=2, is_stable=True
        )
        deg = _jit_count(n)(src)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(deg, dtype=jnp.int32)]
        )
        return offsets, src, dst, wgt

    return jax.jit(fn)


def coo_to_csr_device(src, dst, wgt, *, n: int):
    """Device counting-sort build; returns (offsets, src_s, dst_s, wgt_s).

    Arrays keep their padded length; live edges occupy the prefix (pad
    entries carry src >= n and sort last).
    """
    src = jnp.asarray(src, jnp.int32)
    return _jit_coo_to_csr(int(n), int(src.shape[0]))(
        src, jnp.asarray(dst, jnp.int32), jnp.asarray(wgt, jnp.float32)
    )


@functools.lru_cache(maxsize=None)
def _jit_sort_coo(m: int):
    def fn(src, dst, wgt):
        return jax.lax.sort(
            (src, dst, wgt), dimension=0, num_keys=2, is_stable=True
        )

    return jax.jit(fn)


def sort_coo_device(src, dst, wgt):
    """Device (src, dst) lex sort only — for engines that source their
    degree histogram elsewhere (the Pallas tile kernel)."""
    src = jnp.asarray(src, jnp.int32)
    return _jit_sort_coo(int(src.shape[0]))(
        src, jnp.asarray(dst, jnp.int32), jnp.asarray(wgt, jnp.float32)
    )


# ---------------------------------------------------------------------------
# CSR -> DiGraph arena image (the paper's load-into-representation step)
# ---------------------------------------------------------------------------
def arena_image_host(offsets, dst, wgt, starts, caps, cap_e: int, cap_v: int):
    """Numpy shifted-offset fill of the slotted arena (single pass each).

    ``starts``/``caps`` are the host CP2AA block placement; every edge
    lands at ``starts[row] + (edge_idx - offsets[row])`` and every block
    slot records its owning row.
    """
    o = np.asarray(offsets, np.int64)
    deg = np.diff(o)
    n = deg.shape[0]
    total = int(caps[:n].sum())
    m = int(o[-1])
    a_dst = np.full(cap_e, SENTINEL, np.int32)
    a_wgt = np.zeros(cap_e, np.float32)
    a_rows = np.full(cap_e, cap_v, np.int32)
    if m:
        gidx = np.repeat(starts[:n].clip(0), deg) + (
            np.arange(m) - np.repeat(o[:-1], deg)
        )
        a_dst[gidx] = np.asarray(dst)[:m]
        a_wgt[gidx] = np.asarray(wgt)[:m]
    if total:
        a_rows[:total] = np.repeat(
            np.arange(n, dtype=np.int32), caps[:n].astype(np.int64)
        )
    return a_dst, a_wgt, a_rows


def arena_image(offsets, dst, wgt, starts, caps, cap_e: int, cap_v: int):
    """The arena payload as three jnp arrays: the numpy fill + one transfer.

    Off-TPU it beats XLA CPU scatters (~100ns per scattered slot).  On a
    TPU v5e a fused device fill, whose two ``searchsorted`` over every
    edge and every arena slot are gather loops, took 231 s for the
    scale-22 Graph500 build (1.3e8 edges, 2^28 slots); the host fill
    took seconds.
    """
    a_dst, a_wgt, a_rows = arena_image_host(
        np.asarray(offsets), np.asarray(dst), np.asarray(wgt),
        np.asarray(starts), np.asarray(caps), cap_e, cap_v,
    )
    return jnp.asarray(a_dst), jnp.asarray(a_wgt), jnp.asarray(a_rows)


# ---------------------------------------------------------------------------
# CSR -> flat padded COO image (SortedCOO / LazyCSR base arrays)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _jit_flat_image(cap: int, m: int):
    def fn(offsets, dst, wgt):
        rows = util.expand_rows(offsets, m)
        pad = cap - m
        r = jnp.concatenate([rows, jnp.full((pad,), SENTINEL, jnp.int32)])
        d = jnp.concatenate([dst, jnp.full((pad,), SENTINEL, jnp.int32)])
        w = jnp.concatenate([wgt, jnp.zeros((pad,), jnp.float32)])
        return r, d, w

    return jax.jit(fn)


def flat_image(offsets, dst, wgt, cap: int):
    """(row_ids, dst, wgt) padded to ``cap`` in ONE fused dispatch.

    The row-major flat layout SortedCOO and LazyCSR share; replaces the
    seed's per-buffer expand + three concatenate dispatches.
    """
    m = int(np.asarray(dst).shape[0])
    return _jit_flat_image(int(cap), m)(
        jnp.asarray(offsets, jnp.int32),
        jnp.asarray(dst, jnp.int32),
        jnp.asarray(wgt, jnp.float32),
    )


# ---------------------------------------------------------------------------
# CSR -> ChunkedGraph page image (same fill, PAGE-quantized blocks)
# ---------------------------------------------------------------------------
def pages_image_host(offsets, dst, wgt, page_base, npages, page: int,
                     p_cap: int, n_sentinel: int):
    """Page-pool image: edges land at page_base[row]*page + intra-row idx."""
    o = np.asarray(offsets, np.int64)
    deg = np.diff(o)
    n = deg.shape[0]
    m = int(o[-1])
    pages_d = np.full(p_cap * page, SENTINEL, np.int32)
    pages_w = np.zeros(p_cap * page, np.float32)
    owner = np.full(p_cap, n_sentinel, np.int32)
    if m:
        gidx = np.repeat(page_base[:n] * page, deg) + (
            np.arange(m) - np.repeat(o[:-1], deg)
        )
        pages_d[gidx] = np.asarray(dst)[:m]
        pages_w[gidx] = np.asarray(wgt)[:m]
    total_pages = int(npages[:n].sum())
    if total_pages:
        owner[:total_pages] = np.repeat(
            np.arange(n, dtype=np.int32), npages[:n].astype(np.int64)
        )
    return (
        pages_d.reshape(p_cap, page),
        pages_w.reshape(p_cap, page),
        owner,
    )
