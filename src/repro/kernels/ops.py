"""Jit'd public wrappers for the Pallas kernels.

``interpret_mode`` is the one place that decides how a kernel runs: compiled
on TPU, interpreted on CPU (tests and examples, under
``JAX_PLATFORMS=cpu``), and refused on any other platform — a kernel never
drops to the interpreter on a device it was not written for.  Each op also
has a pure-jnp oracle in ``ref.py``; the ``tests/test_kernels.py`` family
sweeps shapes/dtypes asserting allclose between the two, and
``tests/test_tpu_compile.py`` compiles every kernel for a described v5e.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .dequant_aggregate import dequant_aggregate as _deq_agg
from .flash_attention import flash_attention as _flash
from .grad_aggregate import grad_aggregate as _agg
from .quantize import dequantize as _dequant, quantize as _quant
from .scatter_aggregate import scatter_aggregate as _scatter_agg
from .switch_sum import switch_sum as _switch_sum


def interpret_mode() -> bool:
    """False on TPU (compiled), True on CPU (interpreted); any other
    platform raises."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run compiled on TPU or interpreted "
                       f"on CPU; the default backend is {platform!r}")


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_op(q, k, v, *, causal: bool = True, block_q: int = 128,
                       block_k: int = 128):
    """q: [B, H, Sq, D]; k, v: [B, KVH, Skv, D] -> [B, H, Sq, D]."""
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("block_d",))
def grad_aggregate_op(updates, weights, *, block_d: int = 2048):
    """Weighted-sum N stacked updates + fused ||agg||^2 (one HBM pass).

    A ragged last tile is masked inside the kernel — no pad-to-block copy
    and trailing slice over the full gradient anymore.
    """
    return _agg(updates, weights, block_d=block_d,
                interpret=interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("block", "row_tile", "chunk_n",
                                    "orig_len"))
def dequant_aggregate_op(q, scales, weights, *, block: int = 256,
                         row_tile: int = 512, chunk_n: int = 8,
                         orig_len: Optional[int] = None):
    """Fused aggregator receive path: int8 payloads -> dequantize ->
    weighted sum -> ||agg||^2 in one VMEM-resident pass (the unfused
    composition is ``vmap(dequantize_op)`` + ``grad_aggregate_op``, which
    round-trips N dequantized f32 copies through HBM).  ``q`` is the
    gathered wire, [N, R, 1024], or [N, D] payloads."""
    return _deq_agg(q, scales, weights, block=block, row_tile=row_tile,
                    chunk_n=chunk_n, orig_len=orig_len,
                    interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("d_out", "block_d", "k_tile"))
def scatter_aggregate_op(idx, q, scales, weights, *, d_out: int,
                         block_d: int = 2048, k_tile: int = 256):
    """Sparse receive path for bounded-loss transport: scatter-add N top-k
    int8 chunks (idx [N, K] int32, -1 = dropped slot) into the dense flat
    bucket + fused ||agg||^2, without materializing a dense [D] buffer per
    sender."""
    return _scatter_agg(idx, q, scales, weights, d_out=d_out,
                        block_d=block_d, k_tile=k_tile,
                        interpret=interpret_mode())


@functools.partial(jax.jit,
                   static_argnames=("window", "block_d", "chunk_n",
                                    "orig_len"))
def switch_sum_op(q, *, window: int = 256, block_d: int = 2048,
                  chunk_n: int = 8, orig_len: Optional[int] = None):
    """In-network switch aggregation: windowed int8 member payloads ->
    int32 pod sums (one shared scale makes the integer add exact; the
    int32 widening absorbs fan-in overflow — see switch_sum.py)."""
    return _switch_sum(q, window=window, block_d=block_d, chunk_n=chunk_n,
                       orig_len=orig_len, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("block",))
def quantize_op(x, *, block: int = 256):
    d = x.shape[0]
    pad = (-d) % block
    if pad:
        x = jnp.pad(x, (0, pad))
    q, s = _quant(x, block=block, interpret=interpret_mode())
    return q, s


@functools.partial(jax.jit, static_argnames=("block", "orig_len"))
def dequantize_op(q, scales, *, block: int = 256,
                  orig_len: Optional[int] = None):
    x = _dequant(q, scales, block=block, interpret=interpret_mode())
    return x[:orig_len] if orig_len is not None else x


def compress_update(update_flat: jax.Array, *, block: int = 256):
    """Round-trip helper used by the PS path: returns (payload, ratio)."""
    q, s = quantize_op(update_flat, block=block)
    ratio = update_flat.nbytes / (q.nbytes + s.nbytes)
    return (q, s), ratio


# re-export references for test convenience
flash_attention_ref = ref.flash_attention_ref
grad_aggregate_ref = ref.grad_aggregate_ref
quantize_ref = ref.quantize_ref
dequantize_ref = ref.dequantize_ref
scatter_aggregate_ref = ref.scatter_aggregate_ref
switch_sum_ref = ref.switch_sum_ref
