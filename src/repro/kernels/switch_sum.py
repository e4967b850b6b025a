"""Pallas kernel: windowed fixed-point gradient summation (switch mode).

The in-network aggregation backend (DESIGN.md §13, SwitchML) sums int8
gradient blocks on the pod switch: fixed-point only, a small pool of
window-sized slots, one window drained as soon as every member delivered
it.  This kernel is the data-plane model of that switch: the input is the
pod's gathered wire payload — the same int8 blocks ``quantize.py`` emits,
sharing one scale per pod (``pmax`` of the members' amax) so integer
addition is exact — and the accumulator is **int32**, the
overflow-widening a real switch pipeline applies per packet (int8 lanes
would saturate at two members; int32 holds 2^24 members at full scale).

Layout/streaming: grid ``(D tiles, N chunks)`` with the member-chunk
dimension minor, so each output tile stays VMEM-resident while int8 slabs
stream through double-buffered DMA.
``block_d`` is clamped to whole ``window``s — a D tile is an integer
number of switch slots, the kernel-side image of slot-windowed streaming.
Ragged N chunks are masked via an iota row filter (OOB rows read garbage);
ragged D tiles need no mask — OOB columns only land in OOB output lanes,
which the pipeline drops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _switch_sum_kernel(q_ref, out_ref, *, chunk_n: int, block_d: int,
                       n_total: int):
    j = pl.program_id(1)                       # member chunk (minor: streams)

    q = q_ref[...]                             # [chunk_n, block_d] int8
    # ragged member chunk: rows >= n_total hold garbage (OOB reads)
    row = (jax.lax.broadcasted_iota(jnp.int32, (chunk_n, 1), 0)
           + j * chunk_n)
    widened = jnp.where(row < n_total, q.astype(jnp.int32), 0)
    partial = jnp.sum(widened, axis=0)         # [block_d] int32

    @pl.when(j == 0)
    def _():
        out_ref[...] = partial

    @pl.when(j > 0)
    def _():
        out_ref[...] += partial


def switch_sum(q: jax.Array, *, window: int = 256, block_d: int = 2048,
               chunk_n: int = 8, orig_len: int | None = None,
               interpret: bool = False) -> jax.Array:
    """q: [N, D_pad] int8 (one shared scale) -> int32 sums [orig_len or D_pad].

    ``D_pad`` must be a multiple of ``window`` (it is by construction:
    ``quantize_op`` emits whole blocks and ``window`` is the quantization
    block).  ``block_d`` is clamped to whole windows; ``chunk_n`` need not
    divide N — the trailing member chunk is masked in-kernel.
    """
    n, d_pad = q.shape
    assert q.dtype == jnp.int8, q.dtype
    assert d_pad % window == 0, (d_pad, window)
    d_out = d_pad if orig_len is None else orig_len
    assert 0 < d_out <= d_pad, (d_out, d_pad)
    block_d = min(block_d, d_pad)
    block_d = max(block_d - block_d % window, window)  # whole slot windows
    chunk_n = min(chunk_n, n)
    grid = (pl.cdiv(d_out, block_d), pl.cdiv(n, chunk_n))

    kernel = functools.partial(_switch_sum_kernel, chunk_n=chunk_n,
                               block_d=block_d, n_total=n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((chunk_n, block_d), lambda i, j: (j, i))],
        out_specs=pl.BlockSpec((block_d,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((d_out,), jnp.int32),
        interpret=interpret,
    )(q)
