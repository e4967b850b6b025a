"""Smoke run of the MLfabric training step on a TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip path only

With no option it runs, on the first chip:

1. every Pallas kernel at real widths (a 4 MiB f32 bucket, 4 members, an
   unaligned top-k, qwen2-0.5b attention), compiled, against its pure-jnp
   oracle in ``kernels/ref.py``;
2. the training CLI, ``repro.launch.train.main``, for three steps of
   qwen2-0.5b at its published widths; every loss must be finite;
3. ``launch/steps.build_step`` for qwen2-0.5b on a (pod, data, model) =
   (1, 1, 1) mesh: GSPMD's automatic gradient reduction against the
   MLfabric scheduled path with int8 inter-pod compression, two steps each
   from the same parameters and batches.  The MLfabric program must hold a
   compiled Mosaic kernel (``tpu_custom_call``).

``--chips 4`` runs only the comparison that exists across chips: on a
(2, 2, 1) mesh, the automatic reduction against the MLfabric path with and
without compression, after one step, with the batch and the state spread
over the four devices.

Compile seconds, step seconds and peak device memory are printed for
information; they are not benchmark numbers.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  Where JAX finds no TPU the
script exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import io
import json
import math
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen2-0.5b"
SEQ = 2048
BATCH = 2              # per step on one chip (qwen2-0.5b fits at 2 x 2048)
LR = 0.1
D = 1_048_576          # one 4 MiB f32 bucket, the default ``bucket_bytes``
N = 4                  # aggregated members
KEEP = 0.01            # top-k fraction: K = 10,486 slots, not lane-aligned
BLOCK = 256            # int8 quantization block
HEADS, KV_HEADS, HEAD_DIM = 14, 2, 64   # qwen2-0.5b attention
# One bf16 rounding: forward passes that differ only in how the compiler
# orders bf16 work agree to this relative error.
BF16_REL = 2.0 ** -8
# The int8 round-trip error of a block is at most half a step, amax/254,
# per element, so over a 256-element block its norm is at most
# sqrt(256) / 254 of the block's norm.
INT8_REL = math.sqrt(BLOCK) / 254.0


_START = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _START:7.1f}s] {msg}", flush=True)


def peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 2 ** 30:.2f} GiB"


def timed(fn, *args):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def assert_close(name: str, got, want, **tol) -> None:
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   err_msg=name, **tol)


# --------------------------------------------------------------------------- #
# 1. kernels against their oracles
# --------------------------------------------------------------------------- #
def check_kernels(key: jax.Array) -> None:
    from repro.kernels import ops, ref

    d, n = D, N
    ks = jax.random.split(key, 10)
    w = jax.random.uniform(ks[0], (n,), jnp.float32, 0.5, 1.5)

    def run(name, op, *args):
        out, first = timed(op, *args)
        _, again = timed(op, *args)
        log(f"kernel {name}: first call {first:.3f}s (compile included), "
            f"again {again:.4f}s")
        return out

    u = jax.random.normal(ks[1], (n, d), jnp.float32)
    assert_close("grad_aggregate", run("grad_aggregate", ops.grad_aggregate_op,
                                       u, w),
                 ref.grad_aggregate_ref(u, w), rtol=1e-5, atol=1e-5)

    q = jax.random.randint(ks[2], (n, d), -127, 128, jnp.int32).astype(jnp.int8)
    s = jax.random.uniform(ks[3], (n, d // BLOCK), jnp.float32, 1e-3, 1e-2)
    assert_close("dequant_aggregate",
                 run("dequant_aggregate", ops.dequant_aggregate_op, q, s, w),
                 ref.dequant_aggregate_ref(q, s, w), rtol=1e-5, atol=1e-6)

    got = run("switch_sum", ops.switch_sum_op, q)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.switch_sum_ref(q)))

    x = jax.random.normal(ks[4], (d,), jnp.float32)
    qx, sx = run("quantize", ops.quantize_op, x)
    q_ref, s_ref = ref.quantize_ref(x)
    assert_close("quantize scales", sx, s_ref, rtol=1e-6, atol=0)
    # a division rounded the other way may move a value to the next step
    dq = np.abs(np.asarray(qx, np.int32) - np.asarray(q_ref, np.int32))
    assert dq.max() <= 1, ("quantize", int(dq.max()))
    x_hat = run("dequantize", ops.dequantize_op, qx, sx)
    assert_close("dequantize", x_hat, ref.dequantize_ref(qx, sx),
                 rtol=1e-6, atol=0)
    # round to nearest: within half a step, plus the f32 rounding of x
    x_np = np.asarray(x).reshape(-1, BLOCK)
    err = np.abs(np.asarray(x_hat).reshape(-1, BLOCK) - x_np)
    bound = 0.5 * np.asarray(sx)[:, None] + 2.0 ** -21 * np.abs(x_np)
    assert (err <= bound).all(), "int8 round-trip beyond half a step"

    k = int(round(KEEP * d))
    perm = jax.vmap(lambda kk: jax.random.permutation(kk, d)[:k])(
        jax.random.split(ks[5], n)).astype(jnp.int32)
    idx = jnp.where(jnp.arange(k) % 97 == 0, -1, perm)   # dropped slots
    qk = jax.random.randint(ks[6], (n, k), -127, 128, jnp.int32).astype(
        jnp.int8)
    sk = jax.random.uniform(ks[7], (n,), jnp.float32, 1e-3, 1e-2)
    op = functools.partial(ops.scatter_aggregate_op, d_out=d)
    assert_close(f"scatter_aggregate K={k}",
                 run(f"scatter_aggregate K={k}", op, idx, qk, sk, w),
                 ref.scatter_aggregate_ref(idx, qk, sk, w, d_out=d),
                 rtol=1e-5, atol=1e-6)

    qa = jax.random.normal(ks[8], (1, HEADS, SEQ, HEAD_DIM), jnp.bfloat16)
    kv = jax.random.normal(ks[9], (2, 1, KV_HEADS, SEQ, HEAD_DIM),
                           jnp.bfloat16)
    assert_close("flash_attention",
                 run("flash_attention", ops.flash_attention_op,
                     qa, kv[0], kv[1]),
                 ref.flash_attention_ref(qa, kv[0], kv[1]),
                 rtol=2e-2, atol=2e-2)


# --------------------------------------------------------------------------- #
# 2. the training CLI
# --------------------------------------------------------------------------- #
def check_train_cli(argv) -> None:
    from repro.launch import train

    out = io.StringIO()
    t = time.perf_counter()
    with contextlib.redirect_stdout(out):
        rc = train.main(argv)
    seconds = time.perf_counter() - t
    text = out.getvalue()
    log(text.rstrip())
    assert rc == 0, rc
    losses = [float(v) for v in re.findall(r"loss (\S+)", text)]
    steps = int(argv[argv.index("--steps") + 1])
    assert len(losses) == steps, (losses, steps)
    assert all(math.isfinite(v) for v in losses), losses
    log(f"train CLI: {steps} steps in {seconds:.1f}s (compile included), "
        f"losses {losses}")


# --------------------------------------------------------------------------- #
# 3. automatic reduction against the MLfabric path
# --------------------------------------------------------------------------- #
def _mesh(axis_shapes, devices):
    from jax.sharding import AxisType
    return jax.make_mesh(axis_shapes, ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3, devices=devices)


def run_path(cfg, shape, mesh, batches, **step_kw):
    """Compile one train step, run it over ``batches`` from the seeded
    initial state; return (compiled text, losses, final params, history)."""
    from repro.launch.steps import build_step
    from repro.models import transformer as tf
    from repro.optim.sgd import momentum_sgd_init

    bundle = build_step(cfg, shape, mesh, lr=LR, **step_kw)
    param_sh, opt_sh, batch_sh = bundle.in_shardings
    params = jax.jit(functools.partial(tf.init_params, cfg=cfg),
                     out_shardings=param_sh)(jax.random.key(0))
    opt = jax.jit(momentum_sgd_init, out_shardings=opt_sh)(params)

    t = time.perf_counter()
    compiled = bundle.lower().compile()
    compile_s = time.perf_counter() - t
    losses, step_s = [], []
    for np_batch in batches:
        batch = jax.device_put(np_batch, batch_sh)
        (params, opt, metrics), dt = timed(compiled, params, opt, batch)
        losses.append(float(metrics["loss"]))
        step_s.append(dt)
    log(f"{step_kw}: compile {compile_s:.1f}s, step seconds "
        f"{[round(v, 3) for v in step_s]}, losses {losses}, "
        f"peak {peak_gib(mesh.devices.flat[0])}")
    return compiled.as_text(), losses, params, opt.history, batch


def compare_one_chip(cfg, shape, devices) -> None:
    from repro.data import SyntheticLM

    mesh = _mesh((1, 1, 1), devices[:1])
    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                      seed=0)
    batches = [src.batch(i, shape.global_batch) for i in range(2)]
    auto = run_path(cfg, shape, mesh, batches, grad_path="auto")[1]
    text, mlf = run_path(cfg, shape, mesh, batches, grad_path="mlfabric",
                         compress_inter=True)[:2]
    assert "tpu_custom_call" in text, "MLfabric step holds no Mosaic kernel"
    assert all(math.isfinite(v) for v in auto + mlf), (auto, mlf)
    # step 1 runs the same forward pass on the same parameters
    assert abs(auto[0] - mlf[0]) <= BF16_REL * abs(auto[0]), (auto, mlf)
    # step 2 sees parameters moved by lr * g against lr * int8(g): to first
    # order the loss gap is lr * g.e <= INT8_REL * lr * |g|^2, i.e. a share
    # INT8_REL of the loss change that step 1 made, plus bf16 rounding
    tol = INT8_REL * abs(auto[0] - auto[1]) + BF16_REL * abs(auto[1])
    assert abs(auto[1] - mlf[1]) <= tol, (auto, mlf, tol)
    log(f"one chip: auto {auto} vs mlfabric+int8 {mlf} "
        f"(step-2 tolerance {tol:.4g})")


def _spans(arr, devices) -> bool:
    return {s.device for s in arr.addressable_shards} == set(devices)


def compare_four_chips(cfg, shape, devices) -> None:
    from repro.data import SyntheticLM

    mesh = _mesh((2, 2, 1), devices)
    src = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
                      seed=0)
    batches = [src.batch(0, shape.global_batch)]
    ref = None
    for kw in (dict(grad_path="auto"),
               dict(grad_path="mlfabric", compress_inter=False),
               dict(grad_path="mlfabric", compress_inter=True)):
        _, losses, params, hist, batch = run_path(cfg, shape, mesh, batches,
                                                  **kw)
        for arr in jax.tree.leaves((batch, params, hist)):
            assert _spans(arr, devices), (kw, arr.sharding)
        for arr in jax.tree.leaves(batch):
            rows = {s.index[0].start for s in arr.addressable_shards}
            assert len(rows) == len(devices), (kw, rows)  # one slice each
        params, hist = jax.device_get((params, hist))
        if ref is None:
            ref = (losses, params, hist)
            continue
        assert abs(ref[0][0] - losses[0]) <= BF16_REL * abs(ref[0][0]), \
            (kw, ref[0], losses)
        assert_close(f"params {kw}", params, ref[1], rtol=3e-2, atol=3e-2)
        # the history after one step is -lr * g: the reduced gradient itself
        diff = math.sqrt(sum(float(np.sum((a - b) ** 2)) for a, b in
                             zip(jax.tree.leaves(hist),
                                 jax.tree.leaves(ref[2]))))
        norm = math.sqrt(sum(float(np.sum(b ** 2))
                             for b in jax.tree.leaves(ref[2])))
        # The two programs round the bf16 activations and gradients at
        # different places, and the gap compounds through the backward pass:
        # up to one bf16 unit per layer, plus the int8 round-trip.  (With f32
        # parameters the two paths agree to f32 rounding; see
        # tests/test_dist_path.py.)  A lost or double-counted member would
        # move the gradient by 25 % or more.
        bound = (cfg.n_layers * BF16_REL
                 + (INT8_REL if kw["compress_inter"] else 0.0))
        log(f"four chips {kw}: loss {losses[0]} vs auto {ref[0][0]}, "
            f"|dg|/|g| = {diff / norm:.3g} (bound {bound:.3g})")
        assert diff <= bound * norm, (kw, diff / norm, bound)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.configs import get_config, get_shape
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    log(f"device: {devices[0].device_kind} x {len(devices)}")
    cfg = get_config(ARCH)
    train = get_shape("train_4k")
    t0 = time.perf_counter()
    if args.chips == 4:
        shape = dataclasses.replace(train, seq_len=SEQ, global_batch=4)
        compare_four_chips(cfg, shape, devices[:4])
    else:
        check_kernels(jax.random.key(0))
        log(f"kernels ok, peak {peak_gib(devices[0])}")
        check_train_cli(["--arch", ARCH, "--full", "--steps", "3",
                         "--batch", str(BATCH), "--seq", str(SEQ),
                         "--log-every", "1"])
        shape = dataclasses.replace(train, seq_len=SEQ, global_batch=BATCH)
        compare_one_chip(cfg, shape, devices)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s, "
        f"peak {peak_gib(devices[0])}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
