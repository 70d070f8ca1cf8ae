"""Benchmark harness utilities: timing, graph/table setup, CSV output.

The suites run the paper's graph families at scale 12 on whatever
platform JAX finds, and their rows do not name a device.  The checked-in
``BENCH_*.json`` rows were timed on a one-core CPU container: they
measure XLA's CPU backend at cache-resident sizes, not the TPU, and are
kept as a trajectory of that setting, not as the system's result.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import REPRESENTATIONS, from_coo
from repro.io import synthetic

#: container-scale stand-ins for the paper's Table 1 graph families
GRAPHS = {
    "web_small": dict(kind="web", scale=12, edge_factor=8),
    "social_small": dict(kind="social", scale=12, edge_factor=12),
    "road_small": dict(kind="road", scale=14),
    "uniform_small": dict(kind="uniform", scale=12, edge_factor=8),
}

BATCH_FRACTIONS = (1e-4, 1e-3, 1e-2, 1e-1)


def make_graph(name: str):
    return synthetic.make_graph(seed=42, **GRAPHS[name])


def timeit(fn, *, warmup: int = 1, repeats: int = 3) -> float:
    """Median wall seconds; fn must block on its own result."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def timeit_prepared(
    setup, fn, *, warmup: int = 1, repeats: int = 3, reduce: str = "median"
) -> float:
    """Wall seconds of ``fn(setup())`` with ``setup()`` untimed.

    For in-place mutation benchmarks: ``setup`` builds a fresh victim
    (e.g. a clone) outside the timed region, so the measurement contains
    only the operation itself — no clone-cost subtraction heuristics.
    ``reduce`` picks the estimator: ``median`` (default), or ``min`` for
    rows feeding regression gates — on a CFS-throttled container the
    same program alternates between a fast and a ~2x slow mode, and the
    minimum is the reproducible cost while a 3-sample median is a coin
    flip between modes.
    """
    for _ in range(warmup):
        fn(setup())
    times = []
    for _ in range(repeats):
        state = setup()
        t0 = time.perf_counter()
        fn(state)
        times.append(time.perf_counter() - t0)
    return float(np.min(times) if reduce == "min" else np.median(times))


def emit(rows, header):
    print(",".join(header))
    for r in rows:
        print(",".join(str(r[h]) for h in header))
    return rows
