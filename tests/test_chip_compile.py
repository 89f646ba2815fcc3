"""Compile the served path's kernels and round program for a TPU v5e chip at
the paper's §7.1 scale (λ = 12,208 blocks of R = 8192 records, 8 dims, 2
measures, a 64-slot wave), against a described v5e:2x2 topology.

Nothing runs: the TPU compiler refuses here what the chip would refuse — a
block layout the Pallas lowering rejects, a program that does not fit HBM.
The topology is described inside a module fixture (only the worker that runs
this file loads the TPU library), and the persistent compilation cache is off
around these compiles (an entry written without a chip cannot be read back).
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

LAM, RPB, R_DIMS, S_MEAS, ROWS, SLOTS, GAMMA, UNION = 12_208, 8192, 8, 2, 16, 64, 3, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **kw) -> str:
    return jax.jit(fn, **kw).lower(*args).compile().as_text()


@pytest.mark.parametrize("what", ["dims", "measures"])
def test_block_gather_store_layout_compiles(one_chip, what):
    from repro.kernels.plan_wave import block_gather

    d, dt = (R_DIMS, jnp.int32) if what == "dims" else (S_MEAS, jnp.float32)
    text = _compiled_text(
        functools.partial(block_gather, interpret=False),
        _spec(one_chip, (LAM, d, RPB), dt), _spec(one_chip, (UNION,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_store_union_gather_compiles(one_chip):
    """The store's served gather: dims + measures kernels and the derived
    row-validity mask in one program."""
    from repro.data.block_store import _gather_lane_dense

    text = _gather_lane_dense.lower(
        _spec(one_chip, (LAM, R_DIMS, RPB), jnp.int32),
        _spec(one_chip, (LAM, S_MEAS, RPB), jnp.float32),
        _spec(one_chip, (UNION,), jnp.int32),
        _spec(one_chip, (), jnp.int32),
        interpret=False,
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("kernel", ["density_combine", "density_combine_batch",
                                    "theta_stats_batch"])
def test_repaired_kernels_compile(one_chip, kernel):
    from repro.kernels import density_combine as dc
    from repro.kernels import theta_stats as ts

    dens = _spec(one_chip, (ROWS, LAM), jnp.float32)
    if kernel == "density_combine":
        fn, args = dc.density_combine, (dens, _spec(one_chip, (GAMMA,), jnp.int32))
    elif kernel == "density_combine_batch":
        fn = functools.partial(dc.density_combine_batch, op="or")
        args = (dens, _spec(one_chip, (SLOTS, GAMMA), jnp.int32))
    else:
        fn = ts.theta_stats_batch
        args = (_spec(one_chip, (SLOTS, LAM), jnp.float32),
                _spec(one_chip, (SLOTS, 8), jnp.float32))
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_local_round_program_compiles(one_chip):
    from repro.core.multi_query import _local_round_fn

    compiled = _local_round_fn(RPB).lower(
        _spec(one_chip, (SLOTS, LAM), jnp.float32),
        _spec(one_chip, (SLOTS, LAM), jnp.bool_),
        _spec(one_chip, (SLOTS, LAM), jnp.bool_),
        _spec(one_chip, (SLOTS, 2), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int8),
        _spec(one_chip, (SLOTS,), jnp.float32),
    ).compile()
    assert compiled.memory_analysis() is not None


def test_round_program_has_no_loop(one_chip):
    """The round computes TWO-PRONG window ends by a sort-merge rank: its
    program holds no ``while`` (a per-start binary search is a loop of
    ⌈log2 λ⌉ gathers over ``[64, λ]``)."""
    from repro.core.multi_query import _local_round_fn

    text = _local_round_fn(RPB).lower(
        _spec(one_chip, (SLOTS, LAM), jnp.float32),
        _spec(one_chip, (SLOTS, LAM), jnp.bool_),
        _spec(one_chip, (SLOTS, LAM), jnp.bool_),
        _spec(one_chip, (SLOTS, 2), jnp.int32),
        _spec(one_chip, (SLOTS,), jnp.int8),
        _spec(one_chip, (SLOTS,), jnp.float32),
    ).compile().as_text()
    assert " sort(" in text
    assert re.search(r"\swhile\(", text) is None
