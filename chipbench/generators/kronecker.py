"""Graph500 Kronecker generator (Graph500 specification, section 3).

``edge_factor * 2**scale`` edges; for each of the ``scale`` bits the
source bit is 1 with probability C + D and the destination bit follows
the quadrant probabilities conditioned on it, as in the specification's
reference code.  The bits are drawn on the device.  Vertex labels are
then randomly permuted, as the specification requires, and the result
is symmetrised, deduplicated and self-loop free (LDBC Graphalytics'
treatment of its graph500 data sets).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import graph


def _thresholds(abc):
    a, b, c = abc
    return a + b, c / (1.0 - a - b), a / (a + b)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "abc"))
def edges(key, *, scale: int, edge_factor: int, abc: tuple):
    """Unpermuted Kronecker endpoints, ``edge_factor << scale`` of each."""
    m = edge_factor << scale
    ab, c_norm, a_norm = _thresholds(abc)

    def bit(i, carry):
        src, dst = carry
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        ii = jax.random.uniform(k1, (m,)) > ab
        jj = jax.random.uniform(k2, (m,)) > jnp.where(ii, c_norm, a_norm)
        return (src | (ii.astype(jnp.int32) << i),
                dst | (jj.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, bit, (zero, zero))


def _abc(g):
    return float(g["A"]), float(g["B"]), float(g["C"])


def generate(cfg: dict, seed: int):
    """The graph, and the label permutation that :func:`sample_edges`
    draws new edges through."""
    g = cfg["graph"]
    scale = int(g["scale"])
    src, dst = edges(graph.device_key(seed, 1), scale=scale,
                     edge_factor=int(g["edge_factor"]), abc=_abc(g))
    perm = np.random.default_rng([seed, 1]).permutation(1 << scale)
    perm = perm.astype(np.int32)
    csr = graph.finish(perm[np.asarray(src)], perm[np.asarray(dst)], 1 << scale)
    return csr, {"perm": perm}


def sample_edges(cfg: dict, extras: dict, rng, count: int):
    """``count`` more edges from the same permuted Kronecker distribution."""
    g = cfg["graph"]
    ab, c_norm, a_norm = _thresholds(_abc(g))
    src = np.zeros(count, np.int64)
    dst = np.zeros(count, np.int64)
    for i in range(int(g["scale"])):
        ii = rng.random(count) > ab
        jj = rng.random(count) > np.where(ii, c_norm, a_norm)
        src |= ii.astype(np.int64) << i
        dst |= jj.astype(np.int64) << i
    perm = extras["perm"]
    return perm[src].astype(np.int64), perm[dst].astype(np.int64)
