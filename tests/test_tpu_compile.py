"""Compile-only checks of the graph kernels for a TPU v5e, with no chip.

The TPU compiler is installed wherever libtpu is, and compiles for a
described ``v5e:2x2`` topology without a device attached.  Interpret-mode
parity (test_kernels, test_slot_walk, test_walk_image, test_ingest,
test_updates) cannot see what only the TPU lowering checks: block shapes
against the (8, 128) tiling rule, primitives it has no rule for, vector
layouts it cannot cast, and whether a program fits the chip's memory.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles, since a compile for a described chip cannot be read back.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.csr_build import kernel as cb_kernel
from repro.kernels.slot_update import kernel as su_kernel
from repro.kernels.slot_walk import kernel as sw_kernel
from repro.kernels.slot_walk import ops as sw_ops

#: one-chip served shape: Graph500 web at scale 22 (4.2M vertices,
#: cap_e = 2^28 slots), the walk bound one cap_e/8 step above the built
#: arena's, and the batch the served walk fits at
NV = 1 << 22
CAP_E = 1 << 28
EDGES_HI = 6 * (CAP_E // 8)
BATCH = 4
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    """ShapeDtypeStruct factory placed on one chip of the topology."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_tile_cumsum_compiles(sds):
    rows = BATCH * (EDGES_HI // 128)
    _compile(sw_kernel.tile_cumsum, sds((rows, 128), jnp.float32))


def test_slot_walk_partials_compiles(sds):
    t = EDGES_HI // 128
    _compile(
        lambda r, v: sw_kernel.slot_walk_partials(r, v, sink=NV),
        sds((t, 128), jnp.int32), sds((t, 128), jnp.float32),
    )


@pytest.mark.parametrize("a,w,k", [(64, 128, 8), (16, 1024, 8), (4, 1024, 64)])
def test_merge_rows_pallas_compiles(sds, a, w, k):
    _compile(
        su_kernel.merge_rows_pallas,
        sds((a, w), jnp.int32), sds((a, w), jnp.float32),
        sds((a,), jnp.int32), sds((a, k), jnp.int32),
        sds((a, k), jnp.float32), sds((a, k), jnp.int32),
    )


def test_count_degrees_pallas_compiles(sds):
    nv = 1 << 16
    _compile(
        lambda s: cb_kernel.count_degrees_pallas(s, nv=nv),
        sds((1 << 14, 128), jnp.int32),
    )


def test_served_walk_step_fits_one_chip(sds):
    """The jitted batched walk the server dispatches, Pallas prefix
    engine, at the one-chip served shape: compiles and fits 16 GB."""
    compiled = _compile(
        lambda d, lo, hi, v: sw_ops.slot_walk_multi_blocked(
            d, lo, hi, v, 4, NV, edges_hi=EDGES_HI, engine="pallas"
        ),
        sds((CAP_E,), jnp.int32), sds((NV,), jnp.int32),
        sds((NV,), jnp.int32), sds((BATCH, NV), jnp.float32),
    )
    ma = compiled.memory_analysis()
    total = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )
    assert 0 < total < HBM_BYTES, total
