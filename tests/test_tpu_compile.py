"""The admission path compiled for a described TPU v5e chip.

A CPU run cannot see what the chip's compiler refuses.  These tests
compile the main path's device programs for one v5e chip of a described
``v5e:2x2`` topology — no chip is attached, so nothing runs — at the
paper fleet's shape and at a pod-scale registry.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library, and
every test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import policy_score as ps
from repro.kernels import warm_forecast as wf

SHAPES = [(4, 5), (64, 1024)]      # (functions, platforms)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from
    # the persistent cache without one: keep these compiles out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("f,p", SHAPES)
def test_fused_composite_jit_compiles(one_chip, f, p):
    buf = jax.ShapeDtypeStruct((ps.packed_words(f, p),), jnp.float32,
                               sharding=one_chip)
    compiled = ps._fused_composite_decide_packed.lower(
        buf, f=f, p=p).compile()
    assert compiled.out_info.shape == (2, f)


def test_warm_forecast_tick_compiles(one_chip):
    rows, buckets = 4096, 12
    f32 = jnp.float32

    def s(shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=one_chip)

    vec = s((rows,))
    scalars = [s(())] * 10
    compiled = wf.predictive_tick.lower(
        vec, vec, vec, vec, s((rows, buckets)), vec, *scalars).compile()
    assert [o.shape for o in compiled.out_info] == [
        (rows,), (rows,), (rows,), (rows, buckets), (rows,), (rows,)]
