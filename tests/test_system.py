"""End-to-end behaviour tests for the whole system.

The full MLfabric story on one small problem: a cluster with stragglers and
slow links, async training through the scheduler (ordering + aggregation +
delay bounds), bounded-divergence replication, checkpoint/restart — loss
must go down, invariants must hold.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import BoundedDivergenceReplica, Checkpointer
from repro.configs import get_config, list_configs
from repro.core import C2, N_STATIC, mb
from repro.core.simulator import BandwidthModel, StragglerModel
from repro.data import DataPipeline, SyntheticLM
from repro.models import build_model
from repro.optim import momentum_sgd_init, momentum_sgd_update
from repro.optim.sgd import update_norm
from repro.ps import AsyncTrainer


def test_all_ten_architectures_registered():
    assert len(list_configs()) == 10


def test_end_to_end_async_lm_training():
    """MLfabric-A trains a real (reduced) LM through the full scheduler:
    loss decreases, delays stay bounded, aggregation reduces server bytes."""
    cfg = get_config("qwen2-0.5b").reduced()
    model = build_model(cfg)
    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, seed=0)

    def data_fn(worker, t):   # crc32, not hash(): str hashes vary per run
        cursor = zlib.crc32(worker.encode()) % 997 + t
        return {k: jnp.asarray(v) for k, v in src.batch(cursor, 4).items()}

    eval_batch = {k: jnp.asarray(v) for k, v in src.batch(12345, 8).items()}

    @jax.jit
    def eval_fn(params):
        return model.loss_fn(params, eval_batch)[0]

    params = model.init(jax.random.key(0))
    loss0 = float(eval_fn(params))
    tr = AsyncTrainer(params, model.loss_fn, data_fn, n_workers=4,
                      tau_max=8, base_lr=0.5, gamma=0.0,
                      delay_adaptive=False, update_size=mb(10),
                      compute_time=0.05, straggler=C2, bandwidth=N_STATIC,
                      aggregators=2, eval_fn=eval_fn, has_aux=True, seed=0)
    res = tr.run(until_commits=60)
    assert res.commits >= 60
    assert res.delay_stats["max"] <= 8
    assert res.final_loss < loss0 - 0.2, (loss0, res.final_loss)


def test_end_to_end_train_restart_replicate(tmp_path):
    """SPMD-style loop: train, checkpoint, crash, restart — states and the
    data stream resume exactly; the divergence-bounded replica tracks."""
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)
    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, seed=1)
    pipe = DataPipeline(src, global_batch=4)
    params = model.init(jax.random.key(0))
    opt = momentum_sgd_init(params)
    ck = Checkpointer(str(tmp_path))
    replica = BoundedDivergenceReplica(div_max=5.0, gamma=0.9)

    @jax.jit
    def step_fn(params, opt, batch):
        (_, m), g = jax.value_and_grad(model.loss_fn, has_aux=True)(params,
                                                                    batch)
        gn = update_norm(g)
        p2, o2 = momentum_sgd_update(params, g, opt, lr=0.2, gamma=0.9)
        return p2, o2, m["loss"], gn

    losses = []
    for step in range(6):
        batch = {k: jnp.asarray(v) for k, v in pipe.next_batch().items()}
        params, opt, loss, gn = step_fn(params, opt, batch)
        replica.offer(step, params, float(gn) * 0.2)
        losses.append(float(loss))
        if step == 3:
            ck.save(step + 1, {"params": params, "opt": opt},
                    metadata={"data": pipe.state_dict()})
    assert losses[-1] < losses[0]

    # crash + restart from step 4
    step, state, meta = ck.restore({"params": params, "opt": opt})
    assert step == 4
    pipe2 = DataPipeline(SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32,
                                     seed=1), global_batch=4)
    pipe2.load_state_dict(meta["data"])
    p2, o2 = state["params"], state["opt"]
    for s in range(step, 6):
        batch = {k: jnp.asarray(v) for k, v in pipe2.next_batch().items()}
        p2, o2, loss2, _ = step_fn(p2, o2, batch)
    # restarted run replays the same data and lands at the same loss
    assert abs(float(loss2) - losses[-1]) < 5e-2

    # replica is usable for failover
    rec, rec_step, lost = replica.recover()
    assert rec_step >= 0 and lost >= 0


def test_serve_path_all_subquadratic_archs():
    """The two long_500k-capable archs decode beyond their cache warm-up."""
    for arch in ("rwkv6-1.6b", "jamba-v0.1-52b"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        cache = model.init_cache(1, 16)
        tok = jnp.zeros((1, 1), jnp.int32)
        for pos in range(4):
            logits, cache = model.decode_step(params, cache, tok,
                                              jnp.asarray(pos, jnp.int32))
            tok = jnp.argmax(logits, -1, keepdims=True).astype(jnp.int32)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
