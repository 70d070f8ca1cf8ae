"""Road-network-like graph: a chain plus local shortcuts.

Vertex ``i`` links to ``i + 1``; ``shortcuts`` distinct vertices, one
drawn in each of as many equal strata of the ids, each link to a vertex
2 to 9 ids ahead.  Symmetrised, every edge is distinct, so the directed
edge count is exactly ``2 * (vertices - 1 + shortcuts)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import graph


@functools.partial(jax.jit, static_argnames=("n", "shortcuts"))
def edges(key, *, n: int, shortcuts: int):
    k1, k2 = jax.random.split(key)
    chain = jnp.arange(n - 1, dtype=jnp.int32)
    stratum = (n - 10) / shortcuts
    at = jnp.arange(shortcuts, dtype=jnp.float32) + jax.random.uniform(
        k1, (shortcuts,))
    starts = jnp.floor(at * stratum).astype(jnp.int32)
    hops = jax.random.randint(k2, (shortcuts,), 2, 10, jnp.int32)
    return (jnp.concatenate([chain, starts]),
            jnp.concatenate([chain + 1, starts + hops]))


def generate(cfg: dict, seed: int):
    g = cfg["graph"]
    n = int(g["vertices"])
    src, dst = edges(graph.device_key(seed, 1), n=n,
                     shortcuts=int(g["shortcuts"]))
    return graph.finish(np.asarray(src), np.asarray(dst), n), {}


def sample_edges(cfg: dict, extras: dict, rng, count: int):
    """``count`` local links, each 2 to 9 ids ahead: roads that open."""
    n = int(cfg["graph"]["vertices"])
    src = rng.integers(0, n - 9, count)
    return src, src + rng.integers(2, 10, count)
