"""Pallas kernel: fused weighted gradient aggregation + norm.

This is the MLfabric *aggregator's* compute (paper §4: aggregators compute
the "(weighted) sum" of incoming updates) fused with the squared-norm
reduction the replication algorithm needs (workers/aggregators ship ||u||
with every push, Table 1).  Fusing saves one full HBM pass over the
aggregated gradient — on an aggregator host the op is purely memory-bound,
so the fusion is a straight ~33% traffic cut (read N + write 1 vs read
N + write 1 + read 1).

Tiling: grid over ceil(D/block_d) column tiles; each step stages an
[N, block_d] tile of the stacked updates into VMEM, reduces over N on the
VPU, writes the aggregated tile and adds the tile's sum-of-squares into
one SMEM scalar that stays resident across the (sequential) grid.  A
ragged last tile is masked in-kernel (out-of-bounds lanes are excluded
from the norm; their output writes are dropped by the pipeline), so
callers never pay a pad-to-block copy + slice over the full gradient.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(u_ref, w_ref, out_ref, ssq_ref, *, block_d: int, d: int):
    i = pl.program_id(0)
    u = u_ref[...].astype(jnp.float32)          # [N, block_d]
    w = w_ref[...].astype(jnp.float32)          # [N, 1]
    col = (jax.lax.broadcasted_iota(jnp.int32, (1, block_d), 1)
           .reshape(block_d) + i * block_d)
    valid = col < d
    # OOB columns of a ragged last tile read garbage — zero them so the
    # norm stays exact (their aggregated writes are dropped anyway)
    agg = jnp.sum(jnp.where(valid[None, :], u, 0.0) * w, axis=0)
    out_ref[...] = agg.astype(out_ref.dtype)

    @pl.when(i == 0)
    def _():
        ssq_ref[0] = 0.0

    ssq_ref[0] += jnp.sum(jnp.where(valid, jnp.square(agg), 0.0))


def grad_aggregate(updates: jax.Array, weights: jax.Array, *,
                   block_d: int = 2048, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """updates: [N, D]; weights: [N] -> (agg [D] same dtype, sumsq [] f32).

    Any D works: the last tile is masked in-kernel, not padded in HBM.
    On TPU a ``block_d`` below D must be a multiple of 1024 (the HBM tile
    of a 1-D f32 array).
    """
    n, d = updates.shape
    block_d = min(block_d, d)
    n_blocks = pl.cdiv(d, block_d)

    kernel = functools.partial(_agg_kernel, block_d=block_d, d=d)
    agg, ssq = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((n, block_d), lambda i: (0, i)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_d,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d,), updates.dtype),
            jax.ShapeDtypeStruct((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(updates, weights[:, None])
    return agg, ssq[0]
