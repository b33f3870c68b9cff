"""The Pallas kernels compile for a TPU v5e at real widths.

Each test lowers and compiles one kernel for a described (not attached)
``v5e:2x2`` topology with the TPU compiler, so that a kernel the chip's
compiler would refuse (too much VMEM, an unlowerable primitive, a tile
shape Mosaic cannot take) fails here, at no chip time.  Nothing runs:
inputs are shapes only, and each compile takes a second or two.  One more
test compiles the interp engine's gradient all-reduce and AdamW over the
four chips and reads the layouts the TPU compiler gave them.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

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


# the gradient leaves of bert-base-paper at 2 layers: stacked FFN and
# attention weights, the (unpadded) embedding, a stacked norm scale, a norm
# scale of one layer
BERT_LEAVES = [(2, 2048, 8192), (2, 2048, 16, 128), (30522, 2048), (2, 2048), (2048,)]
_ENTRY_OP = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* (reshape|copy|transpose)\(.*"
    r'op_name="([^"]*)"'
)


def test_tree_all_reduce_keeps_adamw_layouts(topo):
    """The interp all-reduce of a gradient tree, then AdamW, compile for four
    chips with no relayout: no ``reshape``, ``copy`` or ``transpose`` of
    more than 1 MB under ``pccl_*`` or ``train_optimizer`` in the entry
    computation (a leaf flattened for the round loop would need one on the
    way in and one on the way out)."""
    from repro.api import PcclSession
    from repro.core import cost_model as cm
    from repro.train.optimizer import OptimizerConfig, adamw_update, init_opt_state

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    comm = PcclSession(cm.TPU_V5E_PHOTONIC, thread_fabric=False).communicator(
        "data", 4, backend="interp")

    def step(grads, params, opt_state):
        grads = comm.all_reduce(grads)
        with jax.named_scope("train_optimizer"):
            return adamw_update(OptimizerConfig(), grads, params, opt_state)[:2]

    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False))
    rep = NamedSharding(mesh, P())
    leaves = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep) for s in BERT_LEAVES]
    opt = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
                       jax.eval_shape(init_opt_state, leaves))
    text = fn.lower(leaves, leaves, opt).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    big = []
    for line in entry.splitlines():
        m = _ENTRY_OP.match(line)
        if m and ("pccl_" in m.group(4) or "train_optimizer" in m.group(4)):
            size = np.prod([int(d) for d in m.group(2).split(",") if d])
            bits = re.search(r"\d+", m.group(1))  # f32, bf16, s8; pred is a byte
            if size * (int(bits.group()) // 8 if bits else 1) > 1 << 20:
                big.append(line.strip()[:160])
    assert not big, "\n".join(big)
