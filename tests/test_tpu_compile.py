"""Compile every Pallas kernel at real widths for a described TPU v5e.

Interpret mode accepts block shapes that the TPU compiler refuses (a block
whose minor dims break the (8, 128) tiling, a scratch that overflows VMEM),
so the interpret-mode numerics tests cannot catch them.  These tests lower
each kernel with ``interpret=False`` for a v5e chip that is described, not
attached, and check that the compiled program holds a Mosaic custom call.
Nothing runs; ``chip_smoke.py`` checks the numbers on a real chip.

The topology is described inside a fixture: only one process may load the
TPU library, so it must never happen while test modules are imported.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dequant_aggregate import dequant_aggregate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grad_aggregate import grad_aggregate
from repro.kernels.quantize import dequantize, quantize
from repro.kernels.scatter_aggregate import scatter_aggregate
from repro.kernels.switch_sum import switch_sum

D = 1_048_576          # one 4 MiB f32 bucket, the default ``bucket_bytes``
N = 4                  # aggregated members
K = 10_486             # top-k of one bucket at keep=0.01: not lane-aligned
BLOCK = 256            # quantization block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


CASES = {
    "grad_aggregate": (
        functools.partial(grad_aggregate, interpret=False),
        [((N, D), jnp.float32), ((N,), jnp.float32)]),
    "dequant_aggregate": (
        functools.partial(dequant_aggregate, interpret=False),
        [((N, D), jnp.int8), ((N, D // BLOCK), jnp.float32),
         ((N,), jnp.float32)]),
    "switch_sum": (
        functools.partial(switch_sum, interpret=False),
        [((N, D), jnp.int8)]),
    "quantize": (
        functools.partial(quantize, interpret=False),
        [((D,), jnp.float32)]),
    "dequantize": (
        functools.partial(dequantize, interpret=False),
        [((D,), jnp.int8), ((D // BLOCK,), jnp.float32)]),
    "scatter_aggregate": (
        functools.partial(scatter_aggregate, d_out=D, interpret=False),
        [((N, K), jnp.int32), ((N, K), jnp.int8), ((N,), jnp.float32),
         ((N,), jnp.float32)]),
    "flash_attention": (   # qwen2-0.5b: 14 q-heads, 2 kv-heads, head dim 64
        functools.partial(flash_attention, causal=True, interpret=False),
        [((1, 14, 2048, 64), jnp.bfloat16), ((1, 2, 2048, 64), jnp.bfloat16),
         ((1, 2, 2048, 64), jnp.bfloat16)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    text = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, name
