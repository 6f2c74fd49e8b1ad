"""Every Pallas kernel body compiles for a TPU v5e at qwen2-7b widths.

Interpret-mode parity tests cannot see what Mosaic refuses (unaligned
shape casts, block shapes, batched dots), so each case of
``repro.kernels.tpu_cases`` is compiled here ahead of time for a
described ``v5e:2x2`` topology — no chip needed — and must contain a
``tpu_custom_call``, i.e. the kernel did not turn into something else.
"""

import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.tpu_cases import kernel_cases

CASES = kernel_cases()


@pytest.fixture(scope="module")
def topo():
    # libtpu reads this as it loads; unset, the compiler logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("case", CASES, ids=[c.name for c in CASES])
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    shapes = jax.eval_shape(case.make, jax.random.PRNGKey(0))
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
             for s in shapes]
    compiled = jax.jit(case.kernel).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), case.name
