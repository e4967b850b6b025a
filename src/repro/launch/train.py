"""End-to-end training driver (single-host runnable; mesh-agnostic).

Trains any assigned arch (reduced or full config) on the synthetic LM
stream with the paper's optimizer (momentum SGD, eq. 2), checkpoint/restart,
bounded-divergence replication, and either gradient path (GSPMD auto or the
MLfabric scheduled collectives).

Example (CPU, ~100M-param reduced model, a few hundred steps):
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 200 --batch 8 --seq 128 --reduced --ckpt-dir runs/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import BoundedDivergenceReplica, Checkpointer
from ..configs import get_config
from ..data import DataPipeline, SyntheticLM
from ..models import build_model
from ..optim import momentum_sgd_init, momentum_sgd_update, wsd_schedule, \
    cosine_schedule
from ..optim.sgd import update_norm
from .compile_cache import use_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--gamma", type=float, default=0.9)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--div-max", type=float, default=0.0,
                    help=">0 enables the bounded-divergence replica")
    ap.add_argument("--schedule", choices=["wsd", "cosine", "const"],
                    default="cosine")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)

    if args.schedule == "wsd":  # MiniCPM's schedule
        lr_fn = wsd_schedule(args.lr, args.steps // 10, args.steps // 2,
                             args.steps // 3)
    elif args.schedule == "cosine":
        lr_fn = cosine_schedule(args.lr, args.steps // 10, args.steps)
    else:
        lr_fn = lambda s: args.lr

    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq, seed=0)
    pipe = DataPipeline(src, global_batch=args.batch)

    params = model.init(jax.random.key(0))
    opt = momentum_sgd_init(params)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"steps={args.steps} lr={args.lr}")

    start_step = 0
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck and ck.latest_step() is not None:
        start_step, state, meta = ck.restore({"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        pipe.load_state_dict(meta["data"])
        print(f"restored from step {start_step}")

    replica = (BoundedDivergenceReplica(div_max=args.div_max,
                                        gamma=args.gamma)
               if args.div_max > 0 else None)

    @jax.jit
    def step_fn(params, opt, batch, lr):
        (_, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        gnorm = update_norm(grads)
        new_p, new_o = momentum_sgd_update(params, grads, opt, lr=lr,
                                           gamma=args.gamma)
        return new_p, new_o, metrics["loss"], gnorm

    t0 = time.time()
    for step in range(start_step, args.steps):
        np_batch = pipe.next_batch()
        batch = {k: jnp.asarray(v) for k, v in np_batch.items()}
        lr = lr_fn(step)
        params, opt, loss, gnorm = step_fn(params, opt, batch, lr)
        if replica is not None:
            replica.offer(step, params, float(gnorm) * float(lr))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {float(loss):.4f}  "
                  f"lr {float(lr):.2e}  |u| {float(gnorm):.3f}  "
                  f"({time.time()-t0:.1f}s)")
        if ck and (step + 1) % args.ckpt_every == 0:
            ck.save(step + 1, {"params": params, "opt": opt},
                    metadata={"data": pipe.state_dict()})
    if ck:
        ck.save(args.steps, {"params": params, "opt": opt},
                metadata={"data": pipe.state_dict()})
    if replica is not None:
        print(f"replica syncs={replica.syncs} "
              f"savings={replica.replication_savings:.1%}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
