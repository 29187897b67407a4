"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode cannot show what Mosaic refuses: block shapes off the
(8, 128) tiling, 1-D blocks whose tiling differs from XLA's, casts and
reshapes it does not lower, more VMEM than a kernel may use. These tests
compile each kernel at the paper's widths with ``interpret=False`` for a
``v5e:2x2`` topology that is described, not attached, and check that the
compiled program holds the Mosaic kernel (``tpu_custom_call``). Nothing
runs. The topology is only described inside a fixture, after a test of
this file has started: only one process at a time may load the TPU
compiler, and describing it while modules import would make every worker
of a multi-worker run load it.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import EAConfig, make_f15, make_trap
from repro.kernels import ga as gk
from repro.kernels.rastrigin import rastrigin as f15_kernel_mod
from repro.kernels.trap import trap as trap_kernel_mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without the chip, so keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _keys(sharding, n=None):
    k = jax.eval_shape(lambda: jax.random.key(0) if n is None
                       else jax.random.split(jax.random.key(0), n))
    return jax.ShapeDtypeStruct(k.shape, k.dtype, sharding=sharding)


def test_island_kernel_vmapped_fused_trap(one_chip):
    """What every driver runs: the untiled megakernel vmapped over 8
    islands of 1024 x 160 int8 genomes with the trap eval fused in."""
    problem = make_trap(n_traps=40, l=4)
    cfg = EAConfig(max_pop=1024, min_pop=1024)

    def step(keys, pop, fit, size):
        return jax.vmap(lambda k, p, f, s: gk.generation_eval(
            k, p, f, s, cfg, problem.genome, problem.fused,
            interpret=False))(keys, pop, fit, size)

    text = _compiled_text(step, _keys(one_chip, 8),
                          _shape(one_chip, (8, 1024, 160), jnp.int8),
                          _shape(one_chip, (8, 1024), jnp.float32),
                          _shape(one_chip, (8,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,tiled", [(256, False), (10240, True)])
def test_f15_generation_eval(one_chip, n, tiled):
    """F15 at D=1000, m=50: the untiled kernel with the fused eval at an
    island's 256 rows, and the tiled kernel plus the streaming F15 eval
    kernel at the Fig-4 population."""
    problem = make_f15(dim=1000, group=50)
    cfg = EAConfig(max_pop=n, min_pop=n, crossover="blend")
    kw = {"tile_pop": 256, "tile_len": 512} if tiled else {}
    kern = gk.generation_eval_tiled if tiled else gk.generation_eval

    def step(key, pop, fit):
        return kern(key, pop, fit, jnp.int32(n), cfg, problem.genome,
                    problem.fused, interpret=False, consts=problem.consts,
                    **kw)

    text = _compiled_text(step, _keys(one_chip),
                          _shape(one_chip, (n, 1000), jnp.float32),
                          _shape(one_chip, (n,), jnp.float32))
    assert "tpu_custom_call" in text


def test_standalone_trap_eval_kernel(one_chip):
    text = _compiled_text(
        lambda pop: trap_kernel_mod.trap_fitness_kernel(
            pop, n_traps=40, l=4, a=1.0, b=2.0, z=3.0, interpret=False),
        _shape(one_chip, (1024, 160), jnp.int8))
    assert "tpu_custom_call" in text


def test_standalone_f15_eval_kernel(one_chip):
    """The rotation-stack streaming kernel at D=1000, m=50 (m padded to
    the 128-lane width, 20 groups)."""
    text = _compiled_text(
        lambda zp, M: f15_kernel_mod.f15_kernel(zp, M, interpret=False),
        _shape(one_chip, (1024, 20 * 128), jnp.float32),
        _shape(one_chip, (20, 128, 128), jnp.float32))
    assert "tpu_custom_call" in text
