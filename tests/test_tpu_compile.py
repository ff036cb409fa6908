"""Compile the main path's kernels and the fleet engine for a described
TPU v5e chip — no chip attached.

Interpret-mode tests cannot see what the TPU compiler refuses (an
in-kernel ``cumsum``, a block that is not (8, 128)-aligned, too much
VMEM).  These tests lower and compile for the chip's real compiler, so
such a refusal fails here.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and every
test worker imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

SETH_N, SETH_R = 120, 2          # configs/seth.py: 120 nodes x (core, mem)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # an executable compiled for a described chip cannot be read back
    # without one: keep it out of any persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("j", [8, 64])
def test_alloc_score_batch_compiles(one_chip, j):
    from repro.kernels.alloc_score import alloc_score_batch_pallas

    txt = _compile(
        lambda a, c, q: alloc_score_batch_pallas(a, c, q, interpret=False),
        _spec((SETH_N, SETH_R), one_chip), _spec((SETH_N, SETH_R), one_chip),
        _spec((j, SETH_R), one_chip))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("m,n,r", [(64, SETH_N, SETH_R), (512, SETH_N, SETH_R),
                                   (33, 300, 4)])
def test_ebf_shadow_compiles(one_chip, m, n, r):
    from repro.kernels.ebf_shadow import ebf_shadow_pallas

    txt = _compile(
        lambda a, d, q: ebf_shadow_pallas(a, d, q, interpret=False),
        _spec((n, r), one_chip), _spec((m, n, r), one_chip),
        _spec((r,), one_chip))
    assert "tpu_custom_call" in txt


@pytest.fixture(scope="module")
def lane_shapes(one_chip):
    """8 stacked Seth lanes of 256 rows, as FleetRunner pads them."""
    from repro.configs.seth import SYSTEM
    from repro.core.job import JobFactory
    from repro.fleet import SCHED_EBF, FleetRunner
    from repro.workloads.synthetic import SyntheticWorkload

    wl = SyntheticWorkload(
        200, seed=3, mean_interarrival_s=30.0, duration_median_s=1800.0,
        duration_sigma=1.2, node_weights={1: 0.6, 2: 0.3, 8: 0.1},
        resources={"core": (1, 4), "mem": (128, 1024)})
    state = FleetRunner.build("lane", wl, SYSTEM, SCHED_EBF,
                              job_factory=JobFactory()).state
    state = state.pad_to(256, 8, 0, 0)
    return jax.tree.map(lambda x: _spec((8,) + np.shape(x),
                                        one_chip, np.asarray(x).dtype),
                        state)


@pytest.fixture(scope="module")
def bf_key_shapes(one_chip):
    """Seth's Best-Fit key, one copy for the whole batch."""
    from repro.configs.seth import SYSTEM
    from repro.core.resources import ResourceManager
    from repro.fleet.state import bf_key

    key = bf_key(ResourceManager(SYSTEM).capacity)
    return jax.tree.map(lambda x: _spec(x.shape, one_chip, x.dtype), key)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_fleet_advance_compiles(lane_shapes, bf_key_shapes, use_kernel):
    from repro.fleet.engine import advance_fn

    # interpret=False explicitly: this process's backend is the CPU, so
    # the default would resolve to the Pallas interpreter
    fn = jax.vmap(advance_fn(use_kernel=use_kernel, interpret=False),
                  in_axes=(0, None))
    txt = _compile(fn, lane_shapes, bf_key_shapes)
    assert ("tpu_custom_call" in txt) == use_kernel
