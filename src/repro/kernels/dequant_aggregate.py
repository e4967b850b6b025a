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

Layout/streaming: the grid is ``(D tiles, N chunks)`` with the N-chunk
dimension minor, so the output tile stays VMEM-resident while Pallas's
pipeline machinery streams ``[chunk_n, block_d]`` int8 slabs through
double-buffered DMA staging — large buckets and wide fan-ins stream
instead of assert-failing on VMEM.  Both trailing blocks may be ragged:
out-of-bounds rows are masked via the weight vector, out-of-bounds columns
are masked in the norm accumulation (OOB output writes are dropped by the
pipeline itself).  The squared norm accumulates into one SMEM scalar that
stays resident across the whole (sequential) grid.

TPU tiling: a scales tile holds ``block_d // block`` scales on the lane
axis, so a D tile narrower than the bucket must span a multiple of 128
quantization blocks — the default ``block_d`` is exactly 128 blocks of
256.  A bucket that fits in one tile is one full-array block instead.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_kernel(q_ref, s_ref, w_ref, out_ref, ssq_ref, *, block: int,
                  block_d: int, chunk_n: int, n_total: int, d_out: int):
    i = pl.program_id(0)                       # D tile
    j = pl.program_id(1)                       # N chunk (minor: streams)
    n_chunks = pl.num_programs(1)

    q = q_ref[...]                             # [chunk_n, block_d] int8
    s = s_ref[...]                             # [chunk_n, block_d/block]
    w = w_ref[...].astype(jnp.float32)         # [chunk_n, 1]

    # ragged N chunk: rows >= n_total hold garbage (OOB reads) — zero both
    # the weight and the payload so NaN garbage cannot propagate via 0*NaN
    row = (jax.lax.broadcasted_iota(jnp.int32, (chunk_n, 1), 0)
           + j * chunk_n)
    live = row < n_total
    w = jnp.where(live, w, 0.0)
    deq = (q.astype(jnp.float32).reshape(chunk_n, block_d // block, block)
           * s[:, :, None].astype(jnp.float32)).reshape(chunk_n, block_d)
    deq = jnp.where(live, deq, 0.0)
    out_len = out_ref.shape[0]                 # block_d, or d_out if 1 tile
    partial = jnp.sum(deq * w, axis=0)[:out_len]

    @pl.when((i == 0) & (j == 0))
    def _():
        ssq_ref[0] = 0.0

    @pl.when(j == 0)
    def _():
        out_ref[...] = partial

    @pl.when(j > 0)
    def _():
        out_ref[...] += partial

    @pl.when(j == n_chunks - 1)
    def _():
        # ragged D tile: columns >= d_out must not pollute the norm (their
        # output writes are dropped, but the VMEM tile still holds them)
        col = (jax.lax.broadcasted_iota(jnp.int32, (1, out_len), 1)
               .reshape(out_len) + i * block_d)
        agg = out_ref[...]
        ssq_ref[0] += jnp.sum(jnp.where(col < d_out, jnp.square(agg), 0.0))


def dequant_aggregate(q: jax.Array, scales: jax.Array, weights: jax.Array, *,
                      block: int = 256, block_d: int = 32768,
                      chunk_n: int = 8, orig_len: int | None = None,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """q: [N, D_pad] int8; scales: [N, D_pad/block] f32; weights: [N]
    -> (agg f32 [orig_len or D_pad], sumsq [] f32).

    ``D_pad`` must be a multiple of the quantization ``block`` (it is by
    construction: ``quantize_op`` emits whole blocks).  Neither ``block_d``
    nor ``chunk_n`` needs to divide the problem — trailing blocks are
    masked in-kernel, never padded in HBM.
    """
    n, d_pad = q.shape
    assert d_pad % block == 0, (d_pad, block)
    assert scales.shape == (n, d_pad // block), (scales.shape, q.shape)
    d_out = d_pad if orig_len is None else orig_len
    assert 0 < d_out <= d_pad, (d_out, d_pad)
    block_d = min(block_d, d_pad)
    block_d = max(block_d - block_d % block, block)  # whole quant blocks
    chunk_n = min(chunk_n, n)
    grid = (pl.cdiv(d_out, block_d), pl.cdiv(n, chunk_n))
    out_block = d_out if grid[0] == 1 else block_d

    kernel = functools.partial(_fused_kernel, block=block, block_d=block_d,
                               chunk_n=chunk_n, n_total=n, d_out=d_out)
    agg, ssq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk_n, block_d), lambda i, j: (j, i)),
            pl.BlockSpec((chunk_n, block_d // block), lambda i, j: (j, i)),
            pl.BlockSpec((chunk_n, 1), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((out_block,), lambda i, j: (i,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_out,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, scales, weights[:, None])
    return agg, ssq[0]
