"""Pallas kernels: block-wise symmetric int8 gradient (de)quantization.

Gradient compression is the application-level technique the paper lists as
complementary to MLfabric (§8 "quantization of floating point values used
to represent gradients ... MLfabric is complementary") — shipping int8
updates quarters the bytes every scheduled transfer moves, composing
multiplicatively with the scheduling/aggregation wins.

Layout: the quantize kernel writes the int8 wire, rows of ``WIRE_LANES`` =
1024 lanes, four 256-element quantization blocks to a row, and reads the
f32 input in the layout it already has.  A 1-D f32 ``[D]`` is tiled T(1024)
in TPU HBM, which is byte for byte ``[D/128, 128]`` tiled (8, 128): one
(8, 128) tile is one wire row.  So a sublane-strided read of 32 rows at
stride 8 is a dense [32, 128] slab, one lane tile of 32 wire rows, and the
payload leaves as full (32, 128) int8 tiles of ``[D/1024, 1024]`` with no
relayout on either side.  Each lane tile lies in one quantization block, so
a block's scale is a max over its lane tiles, one column per 32 rows; the
columns are the only data moved across lanes and sublanes (a transpose of
a VMEM scratch, 1/256 of the elements), and the scales leave as one
lane-dense 1-D tile.  An input that is not whole rows is padded with zero
blocks (scale 1e-30, payload 0) and the result cut back.  Dequantize is the
inverse on ``[D/block, block]`` rows.
Round-to-nearest-even (VPU native); stochastic rounding is a recorded
follow-up, not needed for the paper's claims.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WIRE_LANES = 1024          # one int8 wire row
LANE_TILE = 128
INT8_TILE_ROWS = 32        # rows of one int8 (32, 128) tile
# quant blocks per grid step (dequantize): a 1-D f32 array is tiled T(1024)
# in TPU HBM, so a partial scales tile must be a multiple of 1024 elements
_ROWS = 1024


def _quant_kernel(x_ref, q_ref, s_ref, col_ref, *, block: int, rows: int):
    per_row = WIRE_LANES // block              # scales per wire row
    width = block // LANE_TILE                 # lane tiles per block

    def group(r0, size):
        """Wire rows [r0, r0 + size) of the tile."""
        for b in range(per_row):
            tiles = [x_ref[pl.ds(8 * r0 + t, size, stride=8), :]
                     .astype(jnp.float32)
                     for t in range(b * width, (b + 1) * width)]
            amax = jnp.max(functools.reduce(jnp.maximum, map(jnp.abs, tiles)),
                           axis=1, keepdims=True)
            scale = jnp.maximum(amax / 127.0, 1e-30)         # [size, 1]
            for k, x in enumerate(tiles):
                lanes = pl.ds((b * width + k) * LANE_TILE, LANE_TILE)
                q = jnp.clip(jnp.round(x / scale), -127, 127)
                q_ref[pl.ds(r0, size), lanes] = q.astype(jnp.int8)
            # col_ref[per_row * r + b] is the scale of block b of row r
            col_ref[pl.ds(per_row * r0 + b, size, stride=per_row), :] = (
                jnp.broadcast_to(scale, (size, LANE_TILE)))

    step = INT8_TILE_ROWS
    n_groups, rem = divmod(rows, step)
    if n_groups:
        def body(g, carry):
            group(pl.multiple_of(g * step, step), step)
            return carry
        jax.lax.fori_loop(0, n_groups, body, 0)
    if rem:                                    # a one-tile payload's tail
        group(n_groups * step, rem)
    s_ref[...] = col_ref[...].T[0]


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...][:, None]
                  ).astype(x_ref.dtype)


def quantize(x: jax.Array, *, block: int = 256, row_tile: int = 512,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: [D] (D % block == 0) -> (q int8 [D], scales f32 [D/block]).

    ``q`` is the wire rows ``[D/1024, 1024]`` flattened: under ``jit``,
    reshaping it back to rows moves nothing.  ``block`` divides a wire row
    into whole 128-lane tiles; ``row_tile`` wire rows make one grid step.
    """
    d = x.shape[0]
    assert d % block == 0, (d, block)
    assert block % LANE_TILE == 0 and WIRE_LANES % block == 0, block
    per_row = WIRE_LANES // block
    # a partial scales tile is whole T(1024) tiles, as in dequantize
    assert (row_tile % INT8_TILE_ROWS == 0
            and row_tile * per_row % _ROWS == 0), (row_tile, block)
    pad = -d % WIRE_LANES
    if pad:
        x = jnp.pad(x, (0, pad))
    n_rows = (d + pad) // WIRE_LANES
    rows = min(row_tile, n_rows)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, block=block, rows=rows),
        grid=(pl.cdiv(n_rows, rows),),
        in_specs=[pl.BlockSpec((8 * rows, LANE_TILE), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, WIRE_LANES), lambda i: (i, 0)),
                   pl.BlockSpec((rows * per_row,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((n_rows, WIRE_LANES), jnp.int8),
                   jax.ShapeDtypeStruct((n_rows * per_row,), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows * per_row, LANE_TILE),
                                   jnp.float32)],
        interpret=interpret,
    )(x.reshape(-1, LANE_TILE))
    q = q.reshape(-1)
    return (q[:d], s[:d // block]) if pad else (q, s)


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
