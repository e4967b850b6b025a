"""Step builders: jitted train / prefill / serve steps for any (arch x shape
x mesh) cell, with full sharding specifications.

These are what the dry-run lowers and what ``train.py`` / ``serve.py`` run.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig
from ..configs.shapes import ShapeConfig
from ..dist import sharding as shd
from ..dist.policy import sharding_policy
from ..models import api as model_api
from ..models import transformer as tf
from ..optim.sgd import MomentumState, momentum_sgd_init, momentum_sgd_update

Params = Any


@dataclass
class StepBundle:
    """A lowered-compilable step: fn + abstract args + shardings."""

    fn: Callable
    args: Tuple                      # abstract ShapeDtypeStruct pytrees
    in_shardings: Tuple
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()

    def jitted(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        return self.jitted().lower(*self.args)


def _opt_shardings(param_sh: Params) -> MomentumState:
    return MomentumState(history=param_sh)


def _metrics_sharding(mesh: Mesh):
    return {"loss": NamedSharding(mesh, P()),
            "aux_loss": NamedSharding(mesh, P()),
            "grad_norm": NamedSharding(mesh, P())}


# --------------------------------------------------------------------------- #
# train
# --------------------------------------------------------------------------- #
def build_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                     lr: float = 1e-3, gamma: float = 0.9,
                     remat: bool = True, microbatches: int = 1) -> StepBundle:
    """``microbatches > 1`` enables gradient accumulation: the global batch
    is processed in sequential slices, dividing activation memory by the
    slice count at the cost of re-gathering FSDP weight shards per slice
    (memory <-> collective trade, EXPERIMENTS.md §Perf iteration 12)."""
    act = shd.activation_policy(cfg, mesh, shape.global_batch)
    assert shape.global_batch % microbatches == 0

    def train_step(params, opt_state, batch):
        with sharding_policy(mesh, act):
            def scalar_loss(p, b):
                total, metrics = tf.loss_fn(p, b, cfg=cfg, remat=remat)
                return total, metrics

            if microbatches == 1:
                (_, metrics), grads = jax.value_and_grad(
                    scalar_loss, has_aux=True)(params, batch)
            else:
                mb = {k: v.reshape(microbatches,
                                   v.shape[0] // microbatches, *v.shape[1:])
                      for k, v in batch.items()}

                def accum(carry, xs):
                    g_acc, loss_acc, aux_acc = carry
                    (_, m), g = jax.value_and_grad(
                        scalar_loss, has_aux=True)(params, xs)
                    g_acc = jax.tree.map(
                        lambda a, b_: a + b_.astype(jnp.float32), g_acc, g)
                    return (g_acc, loss_acc + m["loss"],
                            aux_acc + m["aux_loss"]), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss_sum, aux_sum), _ = jax.lax.scan(
                    accum, (g0, jnp.zeros((), jnp.float32),
                            jnp.zeros((), jnp.float32)), mb)
                grads = jax.tree.map(lambda g: g / microbatches, grads)
                metrics = {"loss": loss_sum / microbatches,
                           "aux_loss": aux_sum / microbatches}

            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)))
            new_params, new_opt = momentum_sgd_update(
                params, grads, opt_state, lr=lr, gamma=gamma)
            out_metrics = {"loss": metrics["loss"],
                           "aux_loss": metrics["aux_loss"],
                           "grad_norm": gnorm}
            return new_params, new_opt, out_metrics

    abstract_params = model_api.params_specs(cfg)
    abstract_opt = jax.eval_shape(momentum_sgd_init, abstract_params)
    batch_specs = model_api.input_specs(cfg, shape)

    param_sh = shd.param_shardings(cfg, mesh, abstract_params)
    opt_sh = _opt_shardings(param_sh)
    batch_sh = shd.batch_shardings(cfg, shape, mesh, batch_specs)

    return StepBundle(
        fn=train_step,
        args=(abstract_params, abstract_opt, batch_specs),
        in_shardings=(param_sh, opt_sh, batch_sh),
        out_shardings=(param_sh, opt_sh, _metrics_sharding(mesh)),
        donate_argnums=(0, 1),
    )


# --------------------------------------------------------------------------- #
# train with the MLfabric gradient path (explicit scheduled collectives)
# --------------------------------------------------------------------------- #
def build_mlfabric_train_step(cfg: ModelConfig, shape: ShapeConfig,
                              mesh: Mesh, *, lr: float = 1e-3,
                              gamma: float = 0.9, remat: bool = True,
                              bucket_bytes: int = 4 * 2 ** 20,
                              shortest_first: bool = True,
                              compress_inter: bool = False,
                              overlap_chunks: int = 1) -> StepBundle:
    """Training step where gradient reduction is the explicit MLfabric
    schedule (flat-bucketed, shortest-first, hierarchical, optionally int8
    cross-pod) instead of GSPMD's automatic all-reduce.

    ``overlap_chunks > 1`` enables the chunked backward: the local batch is
    split into chunks, and each chunk's bucket reductions are issued the
    moment that chunk's gradients exist — barrier-chained in the planner's
    shortest-first order across the whole step — so the inter-pod transfers
    of chunk c overlap with chunk c+1's backprop (XLA sees no dependency
    between them and its latency-hiding scheduler interleaves).  Per-bucket
    results are accumulated as flat vectors and unpacked once at the end.
    The trade: collective *volume* scales with the chunk count (each chunk
    reduces a full-size gradient); ``compress_inter`` quarters that wire
    cost, and the overlap hides it — DESIGN.md §8 records the accounting.

    Batch axes are shard_map-manual; "model" stays auto (GSPMD).  Params
    are replicated over the batch axes in this path (no data-axis FSDP) —
    suitable for the small/mid archs; DESIGN.md §3 records the trade.
    """
    from ..dist.collectives import (plan_reduce, reduce_flat_buckets,
                                    unpack_reduced)

    batch_axes = shd.data_axes(mesh)
    inter = "pod" if "pod" in mesh.axis_names else None
    n_data_shards = 1
    for a in batch_axes:
        n_data_shards *= mesh.shape[a]
    assert overlap_chunks >= 1
    assert (shape.global_batch // n_data_shards) % overlap_chunks == 0, \
        (shape.global_batch, n_data_shards, overlap_chunks)

    # activation policy without batch-axis references (manual inside)
    act = {"residual": P(None, "model", None), "logits": P(None, "model")}
    reduce_kw = dict(intra_axis="data", inter_axis=inter,
                     compress_inter=compress_inter, mean_over=n_data_shards)

    def local_step(params, opt_state, batch):
        layout = plan_reduce(params, bucket_bytes=bucket_bytes,
                             shortest_first=shortest_first)

        def chunk_grads(b):
            with sharding_policy(mesh, act):
                def scalar_loss(p):
                    total, metrics = tf.loss_fn(p, b, cfg=cfg, remat=remat)
                    return total, metrics
                return jax.value_and_grad(scalar_loss, has_aux=True)(params)

        if overlap_chunks == 1:
            (_, metrics), grads = chunk_grads(batch)
            reduced, _ = reduce_flat_buckets(grads, layout, **reduce_kw)
        else:
            chunks = {k: v.reshape(overlap_chunks,
                                   v.shape[0] // overlap_chunks,
                                   *v.shape[1:])
                      for k, v in batch.items()}
            reduced = [jnp.zeros((n,), jnp.float32)
                       for n in layout.bucket_sizes]
            token = jnp.zeros((), jnp.float32)
            loss = aux = jnp.zeros((), jnp.float32)
            for c in range(overlap_chunks):        # unrolled: chunk c+1's
                # backward has no dependency on chunk c's collectives
                (_, m), g = chunk_grads(
                    {k: v[c] for k, v in chunks.items()})
                vecs, token = reduce_flat_buckets(g, layout, token=token,
                                                  **reduce_kw)
                reduced = [r + v for r, v in zip(reduced, vecs)]
                loss = loss + m["loss"]
                aux = aux + m["aux_loss"]
            reduced = [r / overlap_chunks for r in reduced]
            metrics = {"loss": loss / overlap_chunks,
                       "aux_loss": aux / overlap_chunks}
        grads = unpack_reduced(reduced, layout, params)
        new_params, new_opt = momentum_sgd_update(params, grads, opt_state,
                                                  lr=lr, gamma=gamma)
        loss = jax.lax.pmean(metrics["loss"], "data")
        if inter:
            loss = jax.lax.pmean(loss, inter)
        out_metrics = {"loss": loss, "aux_loss": metrics["aux_loss"],
                       "grad_norm": jnp.zeros((), jnp.float32)}
        return new_params, new_opt, out_metrics

    abstract_params = model_api.params_specs(cfg)
    abstract_opt = jax.eval_shape(momentum_sgd_init, abstract_params)
    batch_specs = model_api.input_specs(cfg, shape)

    b = batch_axes
    rep = P()  # params replicated over manual batch axes

    def spec_of(tree, leaf_spec):
        return jax.tree.map(lambda _: leaf_spec, tree)

    in_specs = (spec_of(abstract_params, rep), spec_of(abstract_opt, rep),
                jax.tree.map(lambda l: P(b, *([None] * (l.ndim - 1))),
                             batch_specs))
    out_specs = (spec_of(abstract_params, rep), spec_of(abstract_opt, rep),
                 {"loss": P(), "aux_loss": P(), "grad_norm": P()})

    step = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(batch_axes),
                         check_vma=False)

    # model-axis shardings for the jit boundary (params sharded over model,
    # replicated over batch axes)
    mesh_1pod = mesh
    param_sh = shd.param_shardings(cfg, mesh_1pod, abstract_params)

    def strip_data(ns):
        spec = tuple(None if p in ("data", "pod", ("pod", "data"))
                     else p for p in ns.spec)
        return NamedSharding(mesh, P(*spec))

    param_sh = jax.tree.map(strip_data, param_sh)
    opt_sh = _opt_shardings(param_sh)
    batch_sh = shd.batch_shardings(cfg, shape, mesh, batch_specs)

    return StepBundle(
        fn=step,
        args=(abstract_params, abstract_opt, batch_specs),
        in_shardings=(param_sh, opt_sh, batch_sh),
        out_shardings=(param_sh, opt_sh, _metrics_sharding(mesh)),
        donate_argnums=(0, 1),
    )


# --------------------------------------------------------------------------- #
# prefill
# --------------------------------------------------------------------------- #
def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig,
                       mesh: Mesh) -> StepBundle:
    act = shd.activation_policy(cfg, mesh, shape.global_batch)

    def prefill_step(params, batch):
        with sharding_policy(mesh, act):
            return tf.prefill(params, batch, cfg=cfg)

    abstract_params = model_api.params_specs(cfg)
    batch_specs = model_api.input_specs(cfg, shape)
    param_sh = shd.param_shardings(cfg, mesh, abstract_params)
    batch_sh = shd.batch_shardings(cfg, shape, mesh, batch_specs)

    # output: (logits, cache)
    cache_abs = jax.eval_shape(prefill_step, abstract_params, batch_specs)[1]
    cache_sh = shd.cache_shardings(cfg, mesh, cache_abs, shape.global_batch)
    ba = shd.batch_spec_axes(mesh, shape.global_batch)
    logits_sh = NamedSharding(mesh, P(ba if ba else None, "model"))

    return StepBundle(
        fn=prefill_step,
        args=(abstract_params, batch_specs),
        in_shardings=(param_sh, batch_sh),
        out_shardings=(logits_sh, cache_sh),
    )


# --------------------------------------------------------------------------- #
# decode (serve_step)
# --------------------------------------------------------------------------- #
def build_decode_step(cfg: ModelConfig, shape: ShapeConfig,
                      mesh: Mesh, *, kv_int8: bool = False) -> StepBundle:
    act = shd.activation_policy(cfg, mesh, shape.global_batch)

    def serve_step(params, cache, tokens, pos):
        with sharding_policy(mesh, act):
            return tf.decode_step(params, cache, tokens, pos, cfg=cfg)

    abstract_params = model_api.params_specs(cfg)
    specs = model_api.input_specs(cfg, shape, kv_int8=kv_int8)
    cache_abs, tok_abs, pos_abs = (specs["cache"], specs["tokens"],
                                   specs["pos"])

    param_sh = shd.param_shardings(cfg, mesh, abstract_params)
    cache_sh = shd.cache_shardings(cfg, mesh, cache_abs, shape.global_batch)
    ba = shd.batch_spec_axes(mesh, shape.global_batch)
    tok_sh = NamedSharding(mesh, P(ba if ba else None, None))
    pos_sh = NamedSharding(mesh, P())
    logits_sh = NamedSharding(mesh, P(ba if ba else None, "model"))

    return StepBundle(
        fn=serve_step,
        args=(abstract_params, cache_abs, tok_abs, pos_abs),
        in_shardings=(param_sh, cache_sh, tok_sh, pos_sh),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(1,),
    )


def build_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               grad_path: str = "auto", **kw) -> StepBundle:
    if shape.kind == "train":
        if grad_path == "mlfabric":
            return build_mlfabric_train_step(cfg, shape, mesh, **kw)
        return build_train_step(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh)
    if shape.kind == "decode":
        return build_decode_step(cfg, shape, mesh, **kw)
    raise ValueError(shape.kind)
