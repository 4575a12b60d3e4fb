"""Compile the serving path's Pallas kernel for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
jax, compiles for a topology that is described and not attached, and
refuses what the chip's compiler would refuse (block shapes off the
(8, 128) tiling, layouts Mosaic cannot lower).  Interpret-mode tests
cannot see either.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_decode_attn import paged_decode_attention_kernel

# the paged pool chip_smoke.py serves: llama3-8b widths, 16 lanes of 32
# active pages plus 3 staging slots, 64-token pages
LLAMA = get_config("llama3-8b")
B, P, PAGE = 16, 32 + 3, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library logs under /tmp unless told not to
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, kv_quant):
    H, KVH, hd = LLAMA.num_heads, LLAMA.num_kv_heads, LLAMA.head_dim

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [S((B, H, hd), jnp.bfloat16),
            S((B, P, PAGE, KVH, hd), jnp.bfloat16),
            S((B, P, PAGE, KVH, hd), jnp.bfloat16),
            S((B, P, PAGE), jnp.bool_),
            S((B, P), jnp.int32),
            S((B, P), jnp.bool_)]
    if kv_quant == "int8":
        args += [S((B, P), jnp.int32), S((B, P, 2, KVH), jnp.float32)]
    compiled = jax.jit(paged_decode_attention_kernel).lower(*args).compile()
    print(f"paged decode kernel, kv_quant={kv_quant}:",
          compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
