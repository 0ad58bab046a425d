"""Compile the main path's bucket programs for a TPU v5e that is described,
not attached.

Interpret mode, which every other test runs the Pallas kernels in, accepts
block shapes and memory use that Mosaic refuses; these compiles are what
would fail on the chip.  The topology is described inside a fixture only:
one process at a time may load the TPU compiler's library, and a test worker
that never runs this file must not take it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.executor import _bucket_counter

CHUNK = 32          # WindowExecutor's default chunk: windows per dispatch
CAP_E = 8192        # win_8k edge lane
WIN_8K = (4096, 8192)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            if "TPU_LOG_DIR" not in os.environ:
                mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _compile(sharding, tier, cap_i, cap_j, *, multiset=False, cap_w=0,
             batch=CHUNK):
    fn = _bucket_counter(tier, cap_i, cap_j, cap_w, 512, 256, 512, False,
                         CHUNK, multiset, None)
    lanes = [jax.ShapeDtypeStruct((batch, CAP_E), jnp.int32,
                                  sharding=sharding)
             for _ in range(3 if multiset else 2)]
    lanes.append(jax.ShapeDtypeStruct((batch, CAP_E), jnp.bool_,
                                      sharding=sharding))
    return fn.lower(*lanes).compile()


@pytest.mark.parametrize("multiset", [False, True],
                         ids=["distinct", "multiset"])
@pytest.mark.parametrize("cap_i,cap_j", [(64, 128), (192, 256), WIN_8K])
def test_pallas_window_kernel_compiles(one_chip, cap_i, cap_j, multiset):
    """The 64- and 192-wide rungs come from the align=64 id ladder: their
    blocks are not multiples of 128, and Mosaic must still take them."""
    _compile(one_chip, "pallas", cap_i, cap_j, multiset=multiset)


@pytest.mark.parametrize("tier,cap_w,batch", [
    ("dense", 0, CHUNK),
    # the largest wedge rung the win_8k smoke streams reach; one window, as
    # the served path flushes it
    ("sparse", 2 ** 21, 1),
])
def test_win8k_bucket_counter_compiles(one_chip, tier, cap_w, batch):
    compiled = _compile(one_chip, tier, *WIN_8K, cap_w=cap_w, batch=batch)
    mem = compiled.memory_analysis()
    # what one v5e holds (16 GiB of HBM)
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 2 ** 30


def test_pallas_bucket_program_is_a_mosaic_kernel(one_chip):
    text = _compile(one_chip, "pallas", *WIN_8K).as_text()
    assert "tpu_custom_call" in text
