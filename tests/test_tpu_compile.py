"""The Pallas kernels compile for a TPU v5e at real widths.

Each test lowers and compiles one kernel for a described (not attached)
``v5e:2x2`` topology with the TPU compiler, so that a kernel the chip's
compiler would refuse (too much VMEM, an unlowerable primitive, a tile
shape Mosaic cannot take) fails here, at no chip time.  Nothing runs:
inputs are shapes only, and each compile takes a second or two.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash.kernel import flash_attention_pallas
from repro.kernels.matmul.kernel import matmul_pallas
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.ssd.kernel import ssd_pallas


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, *shapes):
    """Compile ``fn`` for the described chip; return its HLO text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel was not lowered to Mosaic"
    return text


def test_matmul_compiles(one_chip):
    # the chip smoke's shape, and a decode-sized M
    for m in (4096, 8):
        _compile_for_chip(
            matmul_pallas,
            jax.ShapeDtypeStruct((m, 2048), jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((2048, 8192), jnp.bfloat16, sharding=one_chip),
        )


@pytest.mark.parametrize("d,dtype", [
    (2048, jnp.float32),
    (4096, jnp.float32),
    (12288, jnp.float32),
    (12288, jnp.bfloat16),
])
def test_rmsnorm_compiles(one_chip, d, dtype):
    _compile_for_chip(
        rmsnorm_pallas,
        jax.ShapeDtypeStruct((4096, d), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((d,), dtype, sharding=one_chip),
    )


def test_ssd_compiles_at_zamba2_widths(one_chip):
    from repro.configs import get_config

    cfg = get_config("zamba2-2.7b")
    s = cfg.ssm
    heads = s.expand * cfg.d_model // s.head_dim
    B, S = 1, 4096
    _compile_for_chip(
        lambda X, la, Bm, Cm: ssd_pallas(X, la, Bm, Cm, chunk=s.chunk),
        jax.ShapeDtypeStruct((B, S, heads, s.head_dim), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((B, S, heads), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((B, S, s.d_state), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((B, S, s.d_state), jnp.bfloat16, sharding=one_chip),
    )


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16, sharding=one_chip)
    _compile_for_chip(flash_attention_pallas, q, q, q)
