"""Pallas kernels: block-wise symmetric int8 gradient (de)quantization.

Gradient compression is the application-level technique the paper lists as
complementary to MLfabric (§8 "quantization of floating point values used
to represent gradients ... MLfabric is complementary") — shipping int8
updates quarters the bytes every scheduled transfer moves, composing
multiplicatively with the scheduling/aggregation wins.

Layout: x is viewed as [n_blocks, block] rows; each row gets one f32
scale = max|x|/127.  A grid step takes ``_ROWS`` rows at once, so the row
axis of every tile is a multiple of the TPU's sublane tiling and the
scales leave as one lane-dense 1-D tile.  The quantize kernel computes
scale + payload in one VMEM pass; dequantize is the inverse.
Round-to-nearest-even (VPU native); stochastic rounding is a recorded
follow-up, not needed for the paper's claims.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# quant blocks per grid step: a 1-D f32 array is tiled T(1024) in TPU HBM,
# so a partial scales tile must be a multiple of 1024 elements
_ROWS = 1024


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                 # [rows, block]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...][:, None]
                  ).astype(x_ref.dtype)


def quantize(x: jax.Array, *, block: int = 256,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: [D] (D % block == 0) -> (q int8 [D], scales f32 [D/block])."""
    d = x.shape[0]
    assert d % block == 0, (d, block)
    n = d // block
    rows = min(_ROWS, n)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((n, block), jnp.int8),
                   jax.ShapeDtypeStruct((n,), jnp.float32)],
        interpret=interpret,
    )(x.reshape(n, block))
    return q.reshape(d), s


def dequantize(q: jax.Array, scales: jax.Array, *, block: int = 256,
               dtype=jnp.float32, interpret: bool = False) -> jax.Array:
    d = q.shape[0]
    n = d // block
    rows = min(_ROWS, n)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(n, rows),),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                  pl.BlockSpec((rows,), lambda i: (i,))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, block), dtype),
        interpret=interpret,
    )(q.reshape(n, block), scales)
    return x.reshape(d)
