"""Pallas kernel: sparse int8 chunks -> dense weighted aggregate -> norm.

The bounded-loss transport tier (DESIGN.md §12) ships top-k sparsified,
int8-quantized gradient chunks: each sender contributes ``(idx, q, scale)``
— K coordinate positions into the packed flat bucket, their quantized
values, and one per-chunk scale.  The aggregator must scatter-add every
surviving chunk into the dense flat buffer.  Doing that with XLA
``.at[].add`` materializes one dense [D] buffer per sender; this kernel
builds the aggregate in a single pass with the output tile VMEM-resident
while the senders' chunks stream past it.

Grid: ``(D tiles, K tiles)`` with the K axis minor, so each [block_d]
output tile accumulates every sender's sparse chunks before moving on;
a grid step stages one ``[N, k_tile]`` slab of all senders' positions
and values (the sender count is the pod count: small and static).  TPU
has no efficient in-register scatter, so the scatter is the MXU-idiomatic
one-hot matmul: a ``broadcasted_iota`` column ramp down the sublanes is
compared against each sender's positions across the lanes, and the
transposed [block_d, k_tile] one-hot contracts with the dequantized values
on the MXU (``preferred_element_type=f32``, full f32 precision).  Entries
with ``idx < 0`` (dropped / padding slots) match no column and contribute
exactly zero — which is also how K is padded up to a whole number of
lane-aligned tiles; entries ``>= d_out`` land only in the ragged last
tile's dead columns, whose output writes the pipeline drops and whose
norm contribution is masked — so both are safe without a separate mask
pass.

The fused ``||agg||^2`` output feeds replication (Table 1) and the
error-feedback bound accounting for free, like the dense receive path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scatter_kernel(idx_ref, q_ref, s_ref, w_ref, out_ref, ssq_ref, *,
                    block_d: int, n_senders: int, d_out: int):
    i = pl.program_id(0)                       # D tile
    t = pl.program_id(1)                       # K tile (minor: streams)
    n_k_tiles = pl.num_programs(1)
    k_tile = idx_ref.shape[1]

    # [block_d, k_tile]: the output column each sublane stands for
    ramp = jax.lax.broadcasted_iota(jnp.int32, (block_d, k_tile), 0)
    acc = jnp.zeros((1, block_d), jnp.float32)
    for n in range(n_senders):
        pos = idx_ref[n:n + 1, :] - i * block_d             # [1, k_tile]
        vals = q_ref[n:n + 1, :].astype(jnp.float32) * (s_ref[n] * w_ref[n])
        # dropped / padding slots (idx < 0 -> pos < 0) match no column
        onehot_t = (ramp == pos).astype(jnp.float32)
        acc += jax.lax.dot_general(
            vals, onehot_t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    partial = acc.reshape(block_d)

    @pl.when((i == 0) & (t == 0))
    def _():
        ssq_ref[0] = 0.0

    @pl.when(t == 0)
    def _():
        out_ref[...] = partial

    @pl.when(t > 0)
    def _():
        out_ref[...] += partial

    @pl.when(t == n_k_tiles - 1)
    def _():
        # ragged D tile: dead columns must not pollute the norm (their
        # output writes are dropped, but the VMEM tile still holds them)
        col = (jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1)
               .reshape(block_d) + i * block_d)
        agg = out_ref[...]
        ssq_ref[0] += jnp.sum(jnp.where(col < d_out, jnp.square(agg), 0.0))


def scatter_aggregate(idx: jax.Array, q: jax.Array, scales: jax.Array,
                      weights: jax.Array, *, d_out: int,
                      block_d: int = 2048, k_tile: int = 256,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """idx: [N, K] int32 (-1 = dropped slot); q: [N, K] int8;
    scales, weights: [N] f32 -> (agg f32 [d_out], sumsq [] f32).

    Duplicate positions (across senders or within one chunk) accumulate,
    exactly like a dense scatter-add.  ``d_out`` need not be a multiple of
    ``block_d`` — the ragged tail is handled in-kernel — and K need not be
    a multiple of ``k_tile``: the wrapper pads it with ``-1`` slots.  On
    TPU a ``block_d`` below ``d_out`` must be a multiple of 1024 and a
    ``k_tile`` below K a multiple of 128.
    """
    n, k = idx.shape
    assert n >= 1 and k >= 1, (n, k)
    assert q.shape == (n, k), (q.shape, idx.shape)
    assert scales.shape == (n,) and weights.shape == (n,), \
        (scales.shape, weights.shape)
    block_d = min(block_d, d_out)
    k_tile = min(k_tile, k)
    pad = -k % k_tile                          # whole K tiles of -1 slots
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)), constant_values=-1)
        q = jnp.pad(q, ((0, 0), (0, pad)))
    grid = (pl.cdiv(d_out, block_d), (k + pad) // k_tile)

    kernel = functools.partial(_scatter_kernel, block_d=block_d, n_senders=n,
                               d_out=d_out)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    agg, ssq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, k_tile), lambda i, t: (0, t)),
            pl.BlockSpec((n, k_tile), lambda i, t: (0, t)),
            smem,
            smem,
        ],
        out_specs=[
            pl.BlockSpec((block_d,), lambda i, t: (i,)),
            smem,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_out,), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(idx, q, scales.astype(jnp.float32), weights.astype(jnp.float32))
    return agg, ssq[0]
