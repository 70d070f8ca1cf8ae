"""Graph finishing shared by every generator.

A generator draws directed edge endpoints on the device and copies them
to the host once; :func:`finish` symmetrises them, drops self-loops,
sorts by (src, dst), drops duplicates, derives CSR offsets and gives
each undirected pair one weight.  The sort runs on the host: a sort of
~2.7e8 keys costs seconds there, while compiling it for the TPU took
~280 s (compile for a described v5e), paid by every fresh checkout.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np


@dataclasses.dataclass
class HostCSR:
    """A CSR graph on the host: offsets[n+1] int64, dst[m] int32, wgt[m] f32."""

    offsets: np.ndarray
    dst: np.ndarray
    wgt: np.ndarray
    n: int

    @property
    def m(self) -> int:
        return int(self.offsets[-1])

    def src(self) -> np.ndarray:
        return np.repeat(
            np.arange(self.n, dtype=np.int32), np.diff(self.offsets)
        )


def device_key(seed: int, stream: int = 0):
    """A PRNG key from any non-negative seed (``jax.random.key`` keeps only
    the low 32 bits of a larger one)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


def pair_weight(u, v) -> np.ndarray:
    """A weight in (0, 1] that depends only on the unordered pair {u, v},
    so both directions of an edge agree."""
    lo = np.minimum(u, v).astype(np.uint32)
    hi = np.maximum(u, v).astype(np.uint32)
    h = lo * np.uint32(0x9E3779B1) ^ (hi + np.uint32(0x7F4A7C15))
    h = (h ^ (h >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    h = (h ^ (h >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return ((h >> np.uint32(8)).astype(np.float32) + 1.0) * np.float32(1.0 / (1 << 24))


def finish(src: np.ndarray, dst: np.ndarray, n: int) -> HostCSR:
    """Directed endpoints -> the symmetric, simple, weighted CSR graph."""
    s = np.concatenate([src, dst]).astype(np.int64)
    d = np.concatenate([dst, src]).astype(np.int64)
    keep = s != d
    keys = (s[keep] << 32) | d[keep]
    del s, d, keep
    keys.sort()
    first = np.empty(keys.shape[0], bool)
    first[:1] = True
    np.not_equal(keys[1:], keys[:-1], out=first[1:])
    keys = keys[first]
    src_s = (keys >> 32).astype(np.int32)
    dst_s = (keys & 0xFFFFFFFF).astype(np.int32)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src_s, minlength=n), out=offsets[1:])
    return HostCSR(offsets=offsets, dst=dst_s, wgt=pair_weight(src_s, dst_s),
                   n=int(n))
