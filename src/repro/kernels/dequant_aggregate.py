"""Pallas kernel: fused int8 dequantize -> weighted aggregate -> norm.

This is the full MLfabric *aggregator host* data plane for a compressed
inter-pod bucket in ONE pass over the wire payload.  The unfused path
(``quantize.py`` dequantize per update, then ``grad_aggregate.py``) writes
N dequantized f32 arrays to HBM and immediately reads them back:

    unfused:  read N*(D + 4D/block)   [int8 payload + scales]
              write 4*N*D             [dequantized f32 copies]   <- wasted
              read 4*N*D              [aggregate reads them back] <- wasted
              write 4*D               [aggregate + fused norm]
    fused:    read N*(D + 4D/block), write 4*D

The aggregator is purely memory-bound (paper §4: it computes the weighted
sum of incoming updates), so dropping the 8*N*D round-trip is a direct
throughput win — ~6x modeled HBM traffic at N=8 (see
``benchmarks/roofline.py:aggregator_hbm_traffic``).

Layout/streaming: the payload is the int8 wire as the inter-pod all-gather
delivers it, ``[N, R, 1024]``: each sender's quantized bucket of ``D_pad =
1024 R`` elements in the wire rows ``quantize_op`` writes, 1024 lanes and
four 256-element quantization blocks to a row.  The grid is ``(row tiles, N
chunks)`` with the N-chunk dimension minor, so the output tile stays
VMEM-resident while ``[chunk_n, row_tile, 1024]`` int8 slabs stream through
double-buffered DMA.  Inside a tile the kernel walks 32-row groups and the
row's eight 128-lane tiles: each lane tile lies in one quantization block,
so its scale is a per-row column, and the sum over N is an elementwise add
of dense ``[32, 128]`` slabs in N order.  The scales arrive as ``[N,
D_pad/block]`` rows; they are the only data the kernel moves across lanes
and sublanes (a transpose into a VMEM column scratch, 1/256 of the
elements).  The output is the flat f32 aggregate: lane tile ``s`` of wire
row ``r`` is flat row ``8 r + s`` of 128 lanes, written with a sublane
stride of 8, so a ``[8 R, 128]`` f32 array is the 1-D ``[D_pad]`` in HBM
order with no relayout (``quantize_op`` reads its input through the same
view).  Trailing blocks may be ragged: out-of-bounds rows
of a row tile are dropped by the pipeline and masked out of the norm, and
rows of a ragged N chunk are masked out of the sum.  The squared norm
accumulates into one SMEM scalar that stays resident across the whole
(sequential) grid.

TPU tiling: an int8 tile is (32, 128), so ``row_tile`` is a multiple of 32
and every int8 slab and f32 vreg the kernel touches is full; a payload of
fewer rows than one tile is one full-array block.  A scales block holds
``row_tile * 1024 / block`` lanes, a multiple of 128.  ``[N, D]`` operands
(the PS wire round trip, tests) are viewed as wire rows, padded with zero
payload to whole rows when ``D`` is not a multiple of 1024.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .quantize import INT8_TILE_ROWS, LANE_TILE, WIRE_LANES


def _fused_kernel(q_ref, s_ref, w_ref, out_ref, ssq_ref, col_ref, *,
                  block: int, rows: int, chunk_n: int, n_total: int,
                  n_chunks: int, d_out: int, mask_tail: bool):
    i = pl.program_id(0)                       # row tile
    j = pl.program_id(1)                       # N chunk (minor: streams)
    per_row = WIRE_LANES // block              # scales per wire row

    # scales [chunk_n, rows*per_row] -> columns: col_ref[per_row*r + b, n]
    # is the scale of block b of row r of sender n
    col_ref[...] = s_ref[...].T

    @pl.when((i == 0) & (j == 0))
    def _():
        ssq_ref[0] = 0.0

    def group(r0, size, masked, sq):
        """Rows [r0, r0 + size) of the tile: add the chunk's senders in N
        order, store the flat rows, return ``sq`` plus their squares."""
        cols = [col_ref[pl.ds(per_row * r0 + b, size, stride=per_row), :]
                for b in range(per_row)]       # per_row x [size, chunk_n]
        for s in range(WIRE_LANES // LANE_TILE):
            lanes = slice(s * LANE_TILE, (s + 1) * LANE_TILE)
            col = cols[s * LANE_TILE // block]
            flat = pl.ds(8 * r0 + s, size, stride=8)
            acc = None if n_chunks == 1 else jnp.where(
                j == 0, 0.0, out_ref[flat, :])
            for n in range(chunk_n):
                sender = j * chunk_n + n
                term = (q_ref[n, pl.ds(r0, size), lanes].astype(jnp.float32)
                        * col[:, n:n + 1]
                        ) * w_ref[jnp.minimum(sender, n_total - 1)]
                if n_total % chunk_n:          # ragged chunk: rows past N
                    term = jnp.where(sender < n_total, term, 0.0)
                acc = term if acc is None else acc + term
            out_ref[flat, :] = acc
            sq_s = jnp.square(acc)
            if masked:                         # past d_out or past the rows
                row = (jax.lax.broadcasted_iota(jnp.int32, sq_s.shape, 0)
                       + i * rows + r0)
                lane = jax.lax.broadcasted_iota(jnp.int32, sq_s.shape, 1)
                idx = row * WIRE_LANES + s * LANE_TILE + lane
                sq_s = jnp.where(idx < d_out, sq_s, 0.0)
            if size % 8 == 0:
                sq = sq + sq_s.reshape(size // 8, 8, LANE_TILE).sum(axis=0)
            else:                              # fold into the first sublane
                first = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 0) == 0
                sq = sq + jnp.where(first, sq_s.sum(axis=0, keepdims=True),
                                    0.0)
        return sq

    def tile(masked):
        step = INT8_TILE_ROWS
        n_groups, rem = divmod(rows, step)
        sq = jnp.zeros((8, LANE_TILE), jnp.float32)
        if n_groups:
            sq = jax.lax.fori_loop(
                0, n_groups,
                lambda g, sq: group(pl.multiple_of(g * step, step), step,
                                    masked, sq), sq)
        if rem:                                # a one-tile payload's tail
            sq = group(n_groups * step, rem, masked, sq)
        return jnp.sum(sq)

    last_chunk = j == n_chunks - 1
    tail = (last_chunk & (i == pl.num_programs(0) - 1) if mask_tail
            else False)

    @pl.when(jnp.logical_not(tail))
    def _():
        ssq = tile(False)

        @pl.when(last_chunk)
        def _():
            ssq_ref[0] += ssq

    if mask_tail:
        @pl.when(tail)
        def _():
            ssq_ref[0] += tile(True)


def dequant_aggregate(q: jax.Array, scales: jax.Array, weights: jax.Array, *,
                      block: int = 256, row_tile: int = 512,
                      chunk_n: int = 8, orig_len: int | None = None,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """q: [N, R, 1024] int8 wire rows (or [N, D_pad]); scales: [N,
    D_pad/block] f32; weights: [N] -> (agg f32 [orig_len or D_pad],
    sumsq [] f32).

    ``D_pad`` must be a multiple of the quantization ``block`` (it is by
    construction: ``quantize_op`` emits whole blocks), and ``block`` must
    divide a wire row into whole 128-lane tiles.  Neither ``row_tile`` nor
    ``chunk_n`` needs to divide the problem — trailing blocks are masked
    in-kernel, never padded in HBM.
    """
    assert block % LANE_TILE == 0 and WIRE_LANES % block == 0, block
    if q.ndim == 2:                # [N, D] payloads: view as wire rows
        d_in = q.shape[1]
        assert d_in % block == 0, (q.shape, block)
        pad = -d_in % WIRE_LANES
        if pad:
            q = jnp.pad(q, ((0, 0), (0, pad)))
            scales = jnp.pad(scales, ((0, 0), (0, pad // block)))
        q = q.reshape(q.shape[0], -1, WIRE_LANES)
    else:
        d_in = q.shape[1] * WIRE_LANES
    n, n_rows, lanes = q.shape
    assert lanes == WIRE_LANES, q.shape
    assert scales.shape == (n, n_rows * WIRE_LANES // block), (
        scales.shape, q.shape)
    d_out = d_in if orig_len is None else orig_len
    assert 0 < d_out <= d_in, (d_out, d_in)
    per_row = WIRE_LANES // block
    assert (row_tile % INT8_TILE_ROWS == 0
            and row_tile * per_row % LANE_TILE == 0), (row_tile, block)
    rows = min(n_rows, row_tile)
    chunk_n = min(chunk_n, n)
    grid = (pl.cdiv(n_rows, rows), pl.cdiv(n, chunk_n))

    kernel = functools.partial(
        _fused_kernel, block=block, rows=rows, chunk_n=chunk_n, n_total=n,
        n_chunks=grid[1], d_out=d_out,
        mask_tail=n_rows % rows != 0 or n_rows * WIRE_LANES != d_out)
    flat, ssq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk_n, rows, WIRE_LANES), lambda i, j: (j, i, 0)),
            pl.BlockSpec((chunk_n, rows * per_row), lambda i, j: (j, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((8 * rows, LANE_TILE), lambda i, j: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8 * n_rows, LANE_TILE), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows * per_row, chunk_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, scales, weights.astype(jnp.float32))
    agg = flat.reshape(-1)
    return (agg if d_out == agg.shape[0] else agg[:d_out]), ssq[0]
