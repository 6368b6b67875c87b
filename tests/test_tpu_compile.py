"""Compile the three Pallas kernels for a described TPU v5e, no chip needed.

The TPU compiler is installed with jax; it compiles for a chip that is
described and not attached, and refuses what the chip would refuse
(unaligned tiles, too much VMEM) where interpret mode does not.  Shapes
are the published widths ``chip_smoke.py`` runs on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rglru.ops import rglru
from repro.kernels.rwkv6.ops import wkv6


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library writes its logs under /tmp unless told not to
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return fn.lower(*args).compile().as_text()


def test_flash_attention_compiles_for_v5e(one_chip, no_compile_cache):
    qkv = ((1, 2048, 16, 128), jnp.bfloat16)          # olmo-1b
    text = _compiled_text(flash_attention, one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in text


def test_rglru_compiles_for_v5e(one_chip, no_compile_cache):
    ax = ((1, 2048, 2560), jnp.float32)               # recurrentgemma-2b
    text = _compiled_text(rglru, one_chip, ax, ax)
    assert "tpu_custom_call" in text


def test_wkv6_compiles_for_v5e(one_chip, no_compile_cache):
    rkvw = ((1, 32, 2048, 64), jnp.float32)           # rwkv6-1.6b
    text = _compiled_text(wkv6, one_chip, rkvw, rkvw, rkvw, rkvw,
                          ((32, 64), jnp.float32))
    assert "tpu_custom_call" in text
