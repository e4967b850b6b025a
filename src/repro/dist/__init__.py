"""``repro.dist`` — the distribution data plane.

Bridges the ``core/`` control plane (ordering, aggregation, replication
*decisions*) to real JAX execution on a device mesh:

* ``sharding``    — partition policy: params / inputs / caches / activations
* ``policy``      — the ``sharding_policy`` context + ``constrain`` hook
  the model forward passes call
* ``flatbuf``     — flat-bucket layout: one buffer per gradient,
  zero-copy bucket/leaf views, the int8 flat wire round-trip, and the
  bounded-loss wire format (top-k sparsify + ``ErrorFeedback``)
* ``collectives`` — ``mlfabric_grad_reduce``: flat-bucketed,
  shortest-first, hierarchical (optionally int8 cross-pod with the fused
  aggregator kernel) gradient reduction in-graph
* ``elastic``     — mesh rebuild + replica restore on device loss
"""

from . import collectives, elastic, flatbuf, policy, sharding
from .collectives import loss_drop_mask, mlfabric_grad_reduce, plan_buckets
from .flatbuf import (ErrorFeedback, FlatLayout, SparseChunk, pack_leaves,
                      plan_flat_layout, sparse_quantize, topk_sparsify)
from .elastic import ElasticSession, surviving_mesh
from .policy import (PhaseLossCallback, PhaseLossPolicy, constrain,
                     sharding_policy)

__all__ = [
    "collectives", "elastic", "flatbuf", "policy", "sharding",
    "loss_drop_mask", "mlfabric_grad_reduce", "plan_buckets",
    "ErrorFeedback", "FlatLayout", "SparseChunk", "pack_leaves",
    "plan_flat_layout", "sparse_quantize", "topk_sparsify",
    "ElasticSession", "surviving_mesh",
    "PhaseLossCallback", "PhaseLossPolicy", "constrain", "sharding_policy",
]
