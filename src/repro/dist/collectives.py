"""MLfabric gradient reduction as explicit in-graph collectives.

``mlfabric_grad_reduce`` replaces GSPMD's automatic gradient all-reduce
with the schedule the paper's control plane (``core/ordering.py``,
``core/aggregation.py``) plans:

* **Flat buckets** (``dist/flatbuf.py``) — the whole gradient is scattered
  once into a single flat f32 buffer; every planned bucket is then a
  contiguous zero-copy slice of it, so a bucket is one transfer unit in
  the compiled graph exactly as it is one unit in the control plane's
  schedule (paper §4: updates are the unit of transfer).  No per-leaf
  concat/split temporaries survive on the hot path.
* **Shortest-job-first issue order** (Alg. 2, §5.1.1) — buckets are
  reduced smallest-first, and consecutive reductions are chained through
  ``optimization_barrier`` so XLA cannot reorder them: short transfers
  complete early, exactly the avg-completion-time argument of the paper.
* **Hierarchical aggregation** (§5.2) — an intra-pod ``psum`` feeds an
  optional inter-pod stage that mirrors the paper's aggregator hosts:
  every pod ships its partial aggregate (optionally int8-compressed via
  ``kernels/quantize.py``) and each host runs the aggregator compute.
  With compression that receive path is the fused
  ``kernels/dequant_aggregate.py`` kernel: dequantize -> weighted sum ->
  norm in one VMEM-resident pass instead of N dequantized f32 HBM
  round-trips.

The staged API (``plan_reduce`` + ``reduce_flat_buckets``) lets
``launch/steps.py`` overlap communication with a chunked backward: each
chunk's bucket reductions are issued as soon as that chunk's gradients
exist, while the next chunk's backprop runs.

The functions must be called inside a ``shard_map`` body where
``intra_axis`` (and ``inter_axis``, when given) are manual mesh axes —
see ``launch/steps.py:build_mlfabric_train_step``.  Mesh axes that body
leaves automatic (``model``) are made manual around each Pallas kernel
call (``_whole_bucket``): a compiled Mosaic kernel cannot be partitioned
by GSPMD, so every device runs it on the whole bucket.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from .. import scopes
from ..kernels import (dequant_aggregate_op, grad_aggregate_op, quantize_op,
                       scatter_aggregate_op, switch_sum_op)
# Re-exported for backwards compatibility: the bucket planner grew into the
# flat-layout planner and moved to flatbuf.py.
from .flatbuf import (Bucket, FlatLayout, bucket_slice, pack_leaves,
                      plan_buckets, plan_flat_layout, sparse_quantize,
                      topk_sparsify, unpack_bucket)

Params = Any

__all__ = ["Bucket", "plan_buckets", "loss_drop_mask", "mlfabric_grad_reduce",
           "plan_reduce", "reduce_flat_buckets", "unpack_reduced"]

BACKENDS = ("host", "switch", "hierarchical")

_WIRE_LANES = 1024   # int8 wire row: four 256-element quantization blocks


# --------------------------------------------------------------------------- #
# the aggregation hierarchy
# --------------------------------------------------------------------------- #
def _whole_bucket(op: Callable, *args):
    """Call the kernel op ``op`` with every mesh axis manual.

    Inside the step's ``shard_map`` the non-batch axes stay automatic, and
    GSPMD cannot partition a compiled Mosaic kernel.  The remaining axes
    become manual here with the operands replicated over them, so each
    device aggregates the whole bucket (a no-op for size-1 axes).
    """
    mesh = jax.sharding.get_abstract_mesh()
    auto = frozenset(name for name, kind in
                     zip(mesh.axis_names, mesh.axis_types)
                     if kind != AxisType.Manual)
    if not auto:
        return op(*args)
    return jax.shard_map(op, mesh=mesh, in_specs=P(), out_specs=P(),
                         axis_names=auto, check_vma=False)(*args)


def _intra_pod_switch_sum(vec: jax.Array, intra_axis: str, *,
                          window: int = 256) -> jax.Array:
    """Intra-pod stage in switch mode: fixed-point in-network aggregation.

    The pod switch only adds integers (DESIGN.md §13, SwitchML), so the
    members agree on ONE shared scale — ``pmax`` of their amax — quantize
    to int8 against it, and the switch (modeled by the windowed
    ``kernels/switch_sum.py`` pass over the gathered wire payload) emits
    exact int32 sums that any member dequantizes with the same scale.
    Unlike the per-block compression of ``quantize_op``, the shared scale
    makes the integer addition itself lossless: the only error is the one
    initial rounding to the int8 grid.
    """
    d = vec.shape[0]
    vec = vec.astype(jnp.float32)
    amax = jax.lax.pmax(jnp.max(jnp.abs(vec)), intra_axis)
    scale = jnp.maximum(amax / 127.0, 1e-30)
    q = jnp.clip(jnp.round(vec / scale), -127, 127).astype(jnp.int8)
    pad = (-d) % window
    if pad:
        q = jnp.pad(q, (0, pad))
    qs = jax.lax.all_gather(q, intra_axis)       # [W, D_pad] int8 wire
    s = _whole_bucket(
        lambda x: switch_sum_op(x, window=window, orig_len=d), qs)
    return s.astype(jnp.float32) * scale


def loss_drop_mask(loss: Any, src: str, dst: str, t: float,
                   k: int) -> np.ndarray:
    """Derive the sparse wire's per-slot drop mask from the simulator's
    :class:`~repro.core.network.LossSchedule`.

    The schedule is a fluid model — ``instant_loss`` returns an expected
    drop *rate* for the path at ``t`` — so the mask realizes that rate
    deterministically: ``round(drop * k)`` of the ``k`` top-k slots,
    evenly spaced across the payload (a burst on the wire hits slots
    uniformly since top-k order is magnitude order, not position order).
    This replaces the synthetic RNG masks earlier demos fed to
    ``ErrorFeedback.compress`` — the simulator's loss policy and the data
    path now describe the *same* wire, byte-for-byte.
    """
    drop, _ = loss.instant_loss(src, dst, t)
    mask = np.zeros(k, dtype=bool)
    n_drop = int(round(drop * k))
    if n_drop > 0:
        mask[np.floor(np.arange(n_drop) * (k / n_drop)).astype(int)] = True
    return mask


def _quantize_wire(vec: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """int8 wire rows [D/1024, 1024] and scales [1, D/block] of ``vec``."""
    q, s = quantize_op(vec)
    return q.reshape(-1, _WIRE_LANES), s[None]


def _inter_pod_aggregate(vec: jax.Array, inter_axis: str, *,
                         compress: bool) -> jax.Array:
    """Cross-pod stage: gather every pod's partial aggregate and run the
    aggregator's fused compute from ``kernels/``.

    With ``compress`` the wire payload is the int8 blocks + f32 scales
    (the §8-complementary gradient compression); the receiving aggregator
    host runs ONE fused dequantize+aggregate+norm pass over the stacked
    payloads — never materializing per-pod f32 copies in HBM.
    """
    if compress:
        d = vec.shape[0]
        # The int8 wire travels as 1024-lane rows: XLA's TPU compiler takes
        # time proportional to the payload (minutes per bucket) to gather
        # int8 as [1, D] or as [D/256, 256] rows, and none for [D/1024,
        # 1024].  So pad to whole rows of four quantization blocks.
        pad = -d % _WIRE_LANES
        if pad:
            vec = jnp.pad(vec, (0, pad))
        q, s = _whole_bucket(_quantize_wire, vec)
        qs = jax.lax.all_gather(q, inter_axis)       # [P, D_pad/1024, 1024]
        n_pods = qs.shape[0]
        qs = qs.reshape(n_pods, -1)                  # [P, D_pad] int8 wire
        ss = jax.lax.all_gather(s, inter_axis, tiled=True)  # [P, D_pad/block]
        agg, _ = _whole_bucket(
            lambda q_, s_, w_: dequant_aggregate_op(q_, s_, w_, orig_len=d),
            qs, ss, jnp.ones((n_pods,), jnp.float32))
        return agg
    gathered = jax.lax.all_gather(vec, inter_axis)   # [P, D] f32 wire
    n_pods = gathered.shape[0]
    agg, _ = _whole_bucket(grad_aggregate_op, gathered,
                           jnp.ones((n_pods,), jnp.float32))
    return agg


def _inter_pod_aggregate_sparse(vec: jax.Array, inter_axis: str, *,
                                keep: float,
                                drop_mask: Optional[Any] = None
                                ) -> jax.Array:
    """Bounded-loss cross-pod stage: every pod ships only its top-k
    coordinates as ``(idx int32, q int8, scale f32)`` and the receiving
    host scatter-adds the sparse chunks into the dense bucket with the
    fused ``kernels/scatter_aggregate.py`` pass (one VMEM-resident sweep,
    no per-pod dense reconstruction).

    The wire shrinks to ``keep * (4 + 1) / 4`` of the dense f32 payload.
    What this drops is redundant small-magnitude mass, which the sender's
    ``ErrorFeedback`` state (``dist/flatbuf.py``) carries into its next
    update; the kernel also tolerates transport-dropped slots marked
    ``idx = -1``, which is how the simulator's bounded policy and this
    data path describe the same wire format.  ``drop_mask`` (bool [>=K],
    typically from :func:`loss_drop_mask`) marks the slots the transport
    lost in flight — they become ``idx = -1`` on the wire, exactly what
    the receive kernel skips.
    """
    d = vec.shape[0]
    k = max(1, min(d, int(round(keep * d))))
    idx, vals = topk_sparsify(vec, k)
    if drop_mask is not None:
        drop = jnp.asarray(drop_mask, bool).ravel()[:k]
        if drop.shape[0] < k:
            drop = jnp.pad(drop, (0, k - drop.shape[0]))
        idx = jnp.where(drop, -1, idx)
    q, scale = sparse_quantize(vals)
    idxs = jax.lax.all_gather(idx, inter_axis)       # [P, K] int32 wire
    qs = jax.lax.all_gather(q, inter_axis)           # [P, K] int8 wire
    ss = jax.lax.all_gather(scale, inter_axis)       # [P] f32
    n_pods = qs.shape[0]
    agg, _ = _whole_bucket(
        lambda i_, q_, s_, w_: scatter_aggregate_op(i_, q_, s_, w_, d_out=d),
        idxs, qs, ss, jnp.ones((n_pods,), jnp.float32))
    return agg


# --------------------------------------------------------------------------- #
# staged flat-bucket reduction
# --------------------------------------------------------------------------- #
def plan_reduce(tree: Params, *, bucket_bytes: int,
                shortest_first: bool = True) -> FlatLayout:
    """Plan the flat-bucket layout for a gradient pytree (f32 transfer)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return plan_flat_layout([l.size for l in leaves], bucket_bytes,
                            elem_bytes=4, shortest_first=shortest_first)


def reduce_flat_buckets(grads: Params, layout: FlatLayout, *,
                        intra_axis: str, inter_axis: Optional[str],
                        compress_inter: bool, mean_over: int,
                        keep_inter: Optional[float] = None,
                        backend: str = "host",
                        drop_mask_inter: Optional[
                            Union[Callable[[int], Any], Any]] = None,
                        token: Optional[jax.Array] = None
                        ) -> Tuple[List[jax.Array], jax.Array]:
    """Pack ``grads`` flat and reduce every bucket in issue order.

    Returns the reduced bucket vectors (in ``layout.buckets`` order) and
    the chain token.  Threading ``token`` across calls extends the SJF
    barrier chain over multiple gradient chunks, which is how the chunked
    backward keeps all its collectives in one planned issue order.

    ``backend`` picks the aggregation mode, mirroring the control plane's
    :class:`~repro.core.backends.AggregationBackend` seam: ``"host"`` is
    the f32 intra-pod ``psum``; ``"switch"`` replaces it with the
    fixed-point in-network sum (``_intra_pod_switch_sum``);
    ``"hierarchical"`` additionally forces the compressed inter-pod stage
    — pods ship int8 pseudo-updates to host aggregators, the same
    two-tier shape the simulator's hierarchical backend plans.

    ``drop_mask_inter`` feeds the sparse (``keep_inter``) stage's per-slot
    transport drops: either a bool mask or a callable ``k -> mask`` (e.g.
    ``functools.partial(loss_drop_mask, loss, src, dst, t)``) since the
    top-k slot count varies per bucket.

    The work is named on the device (``repro.scopes``): the pack under
    ``pack``, each bucket under ``bucket<kk>`` in issue order, with its
    intra-pod sum under ``intra`` and its inter-pod stage under ``inter``.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
    if backend == "hierarchical":
        compress_inter = True
    leaves = jax.tree_util.tree_leaves(grads)
    with jax.named_scope(scopes.PACK):
        flat = pack_leaves(leaves)                   # single fused scatter
    if token is None:
        token = jnp.zeros((), jnp.float32)
    reduced: List[jax.Array] = []
    for k in range(len(layout.buckets)):
        with jax.named_scope(scopes.bucket(k)):
            vec = bucket_slice(flat, layout, k)      # zero-copy view
            # Chain each bucket on the previous one's result: the compiler
            # must issue the collectives in the planned (SJF) order.
            vec, token = jax.lax.optimization_barrier((vec, token))
            with jax.named_scope(scopes.INTRA):
                if backend == "host":
                    vec = jax.lax.psum(vec, intra_axis)  # intra-pod reduce
                else:
                    vec = _intra_pod_switch_sum(vec, intra_axis)
            if inter_axis is not None:
                with jax.named_scope(scopes.INTER):
                    if keep_inter is not None:
                        d_bkt = vec.shape[0]
                        k_top = max(1, min(d_bkt,
                                           int(round(keep_inter * d_bkt))))
                        mask = (drop_mask_inter(k_top)
                                if callable(drop_mask_inter)
                                else drop_mask_inter)
                        vec = _inter_pod_aggregate_sparse(
                            vec, inter_axis, keep=keep_inter, drop_mask=mask)
                    else:
                        vec = _inter_pod_aggregate(vec, inter_axis,
                                                   compress=compress_inter)
            vec = vec / mean_over
            token = vec[0] * 0.0
        reduced.append(vec)
    return reduced, token


def unpack_reduced(reduced: List[jax.Array], layout: FlatLayout,
                   tree: Params) -> Params:
    """Carve the reduced bucket vectors back into ``tree``'s structure
    (zero-copy sub-slices of each bucket)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    with jax.named_scope(scopes.UNPACK):
        for k, vec in enumerate(reduced):
            for i, leaf in unpack_bucket(vec, layout, k, leaves):
                out[i] = leaf
    return jax.tree_util.tree_unflatten(treedef, out)


def mlfabric_grad_reduce(grads: Params, *, intra_axis: str = "data",
                         inter_axis: Optional[str] = None,
                         bucket_bytes: int = 4 * 2 ** 20,
                         shortest_first: bool = True,
                         compress_inter: bool = False,
                         keep_inter: Optional[float] = None,
                         backend: str = "host",
                         drop_mask_inter: Optional[
                             Union[Callable[[int], Any], Any]] = None,
                         mean_over: int = 1) -> Params:
    """Scheduled hierarchical mean of a gradient pytree.

    Numerically equivalent (to f32 reduction tolerance; int8 tolerance
    with ``compress_inter`` or a switch ``backend``) to
    ``psum(grads) / mean_over`` over the batch axes, but executed as an
    explicit flat-bucket schedule.  ``backend`` selects the intra-pod
    aggregation mode ("host" f32 psum, "switch"/"hierarchical"
    fixed-point in-network sum — see ``reduce_flat_buckets``).  With
    ``keep_inter`` the cross-pod stage ships only each pod's top-k
    fraction (the bounded-loss wire format) — deliberately lossy; pair it
    with per-sender ``ErrorFeedback`` to carry the dropped mass forward,
    and ``drop_mask_inter`` to realize the simulator's transport drops on
    this wire.
    """
    if not jax.tree_util.tree_leaves(grads):
        return grads
    layout = plan_reduce(grads, bucket_bytes=bucket_bytes,
                         shortest_first=shortest_first)
    with jax.named_scope(scopes.EXCHANGE):
        reduced, _ = reduce_flat_buckets(
            grads, layout, intra_axis=intra_axis, inter_axis=inter_axis,
            compress_inter=compress_inter, keep_inter=keep_inter,
            backend=backend, drop_mask_inter=drop_mask_inter,
            mean_over=mean_over)
        return unpack_reduced(reduced, layout, grads)
