"""The Pallas kernels compile through Mosaic for a described v5e chip.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: a reshape that splits the lane axis, a lane reversal, a block that
does not fit VMEM. Here each kernel is lowered with ``interpret=False`` at a
real width and compiled for a v5e chip that is described, not attached, and
the compiled HLO must hold the kernel as a ``tpu_custom_call``. Nothing runs.

The topology is described inside a module fixture, never at import: only the
worker that runs this file loads the TPU compiler. The persistent compile
cache is off around these compiles (an entry written for a described chip
cannot be read back without one).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitonic import kernel as bitonic
from repro.kernels.merge_path import kernel as merge_path
from repro.kernels.searchsorted import kernel as searchsorted


@pytest.fixture(scope="module")
def topo():
    # only a missing TPU compiler skips; any other failure to describe the
    # chip (a libtpu load conflict, say) fails the tests that need it
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler")
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _splitter_ranks(sds):
    n, s = 65536, 15
    return (
        lambda x, k, pr, ix, me: searchsorted.splitter_ranks(x, k, pr, ix, me, block=2048),
        (sds((n,)), sds((s,)), sds((s,)), sds((s,)), sds(())),
    )


def _bitonic_sort_tiles(sds):
    return bitonic.bitonic_sort_tiles, (sds((64, 16384)),)


def _bitonic_sort_kv_tiles(sds):
    return bitonic.bitonic_sort_kv_tiles, (sds((64, 16384)), sds((64, 16384)))


def _merge_sorted_tiles(sds):
    return merge_path.merge_sorted_tiles, (sds((64, 8192)), sds((64, 8192)))


@pytest.mark.parametrize(
    "case",
    [_splitter_ranks, _bitonic_sort_tiles, _bitonic_sort_kv_tiles, _merge_sorted_tiles],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_kernel_compiles_for_v5e(one_chip, case):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    fn, args = case(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
