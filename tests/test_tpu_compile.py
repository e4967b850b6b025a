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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

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
WIRE = 1024            # lanes of one int8 wire row


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    described = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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
    "dequant_aggregate_wire": (      # two pods' gathered 4 MiB buckets
        functools.partial(dequant_aggregate, interpret=False),
        [((2, D // WIRE, WIRE), jnp.int8), ((2, D // BLOCK), jnp.float32),
         ((2,), jnp.float32)]),
    "dequant_aggregate_wire_ragged": (   # rows not a multiple of the tile
        functools.partial(dequant_aggregate, orig_len=1000 * WIRE - 77,
                          interpret=False),
        [((2, 1000, WIRE), jnp.int8), ((2, 1000 * WIRE // BLOCK),
                                       jnp.float32), ((2,), jnp.float32)]),
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


def _producer(text, name):
    """The instruction that defines ``%name`` in HLO ``text``: (type,
    opcode, operand names)."""
    line = re.search(r"^\s*(?:ROOT )?%" + re.escape(name) + r" = (.*)$",
                     text, re.M)
    assert line, name
    op = re.search(r" ([a-z][\w-]*)\(([^)]*)\)", line.group(1))
    return (line.group(1)[:op.start()], op.group(1),
            re.findall(r"%([\w.-]+)", op.group(2)))


def _source(text, name):
    """``_producer`` of ``%name`` after looking through bitcasts and tuple
    reads, which move no data: (name, type, opcode)."""
    while True:
        ty, op, operands = _producer(text, name)
        if op not in ("bitcast", "get-tuple-element"):
            return name, ty, op
        name = operands[0]


def test_int8_wire_reaches_kernel_as_gathered(topo, monkeypatch):
    """The compressed inter-pod stage on a (pod 2, data 2) v5e:2x2 mesh:
    the int8 wire keeps one layout from ``quantize_op`` through the
    all-gather to the receive kernel, with no s8 reshape, copy or transpose
    anywhere on the way."""
    from repro.dist.collectives import plan_reduce, reduce_flat_buckets
    from repro.kernels import ops
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("pod", "data"),
                axis_types=(AxisType.Explicit,) * 2)
    # a 4 MiB bucket, and a bucket that ends inside a wire row
    tree = {"a": jax.ShapeDtypeStruct((D,), jnp.float32),
            "b": jax.ShapeDtypeStruct((900_000,), jnp.float32)}
    layout = plan_reduce(tree, bucket_bytes=4 * 2 ** 20)

    def body(grads):
        return reduce_flat_buckets(grads, layout, intra_axis="data",
                                   inter_axis="pod", compress_inter=True,
                                   mean_over=4)[0]

    step = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)
    args = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                    sharding=NamedSharding(mesh, P()))
            for k, v in tree.items()}
    text = jax.jit(step).lower(args).compile().as_text()

    kernels = re.findall(r"^\s*%(dequant_aggregate_op[\w.]*) = .*"
                         r"custom_call_target=\"tpu_custom_call\"", text, re.M)
    assert len(kernels) == len(layout.buckets), kernels
    for name in kernels:
        _, _, operands = _producer(text, name)
        gather, wire_type, op = _source(text, operands[0])
        assert wire_type.startswith("s8[") and op.startswith("all-gather"), (
            name, wire_type, op)
        _, _, (sent,) = _producer(text, gather)
        sender, _, op = _source(text, sent)
        assert sender.startswith("quantize_op") and op == "custom-call", (
            name, sender, op)
    relayouts = re.findall(r"^\s*%([\w.-]+) = s8\S* (reshape|copy|transpose)"
                           r"\(", text, re.M)
    assert not relayouts, relayouts
