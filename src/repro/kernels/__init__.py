"""Pallas TPU kernels for the framework's compute hot-spots.

* ``flash_attention``    — blockwise online-softmax attention (attn archs)
* ``grad_aggregate``     — fused weighted-sum + norm (the aggregator op)
* ``dequant_aggregate``  — fused int8 dequantize + weighted-sum + norm
                           (the aggregator's *receive* path for compressed
                           inter-pod buckets; streams over N in VMEM)
* ``quantize``           — int8 block quantization (gradient compression)
* ``scatter_aggregate``  — sparse top-k int8 chunks -> dense scatter-add
                           + norm (the bounded-loss transport receive path)
* ``switch_sum``         — windowed int8 -> int32 fixed-point summation
                           (the SwitchML-style in-network aggregation mode)

Each has: the kernel (pl.pallas_call + BlockSpec), a jit wrapper in
``ops.py`` (compiled on TPU, interpreted on CPU, refused elsewhere — see
``ops.interpret_mode``), and a pure-jnp oracle in ``ref.py``.
"""

from .ops import (compress_update, dequant_aggregate_op, dequantize_op,
                  flash_attention_op, grad_aggregate_op, quantize_op,
                  scatter_aggregate_op, switch_sum_op)

__all__ = ["compress_update", "dequant_aggregate_op", "dequantize_op",
           "flash_attention_op", "grad_aggregate_op", "quantize_op",
           "scatter_aggregate_op", "switch_sum_op"]
