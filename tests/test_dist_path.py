"""Distribution-layer tests: sharding rules + the MLfabric gradient path.

The multi-device tests run in a subprocess (XLA_FLAGS must be set before
jax initializes, which pytest has already done in this process).
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from repro.configs import get_config
from repro.dist import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.models import params_specs


def test_param_shardings_cover_every_leaf():
    """Every arch's param tree gets a full-rank PartitionSpec per leaf."""
    mesh = make_host_mesh()
    for arch in ("qwen2-7b", "deepseek-v2-236b", "jamba-v0.1-52b",
                 "rwkv6-1.6b", "whisper-tiny"):
        cfg = get_config(arch)
        abstract = params_specs(cfg)
        sh = shd.param_shardings(cfg, mesh, abstract)
        flat_a = jax.tree.leaves(abstract)
        flat_s = jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))
        assert len(flat_a) == len(flat_s)
        for a, s in zip(flat_a, flat_s):
            assert len(s.spec) <= a.ndim, (arch, a.shape, s.spec)


def test_head_policy_selection():
    mesh = make_host_mesh()  # model axis size 1 -> everything divisible
    assert shd.head_policy(get_config("stablelm-1.6b"), mesh)


def test_batch_axes_fallback():
    mesh = make_host_mesh()
    assert shd.batch_spec_axes(mesh, 16) is not None


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, get_shape
    from jax.sharding import AxisType
    from repro.launch.steps import build_step
    from repro.optim.sgd import momentum_sgd_init
    from repro.models import transformer as tf

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("stablelm-1.6b").reduced()
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=128,
                                global_batch=4)
    params = tf.init_params(jax.random.key(0), cfg)
    opt = momentum_sgd_init(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 128)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 128)),
                                   jnp.int32)}
    outs = {}
    cases = {"auto": dict(grad_path="auto"),
             "mlfabric": dict(grad_path="mlfabric"),
             "mlfabric_overlap": dict(grad_path="mlfabric",
                                      overlap_chunks=2)}
    for path, kw in cases.items():
        b = build_step(cfg, shape, mesh, lr=0.1, **kw)
        f = jax.jit(b.fn, in_shardings=b.in_shardings,
                    out_shardings=b.out_shardings)
        p2, o2, m = f(jax.device_get(params), jax.device_get(opt), batch)
        outs[path] = (jax.device_get(p2), float(m["loss"]))
    (pa, la) = outs["auto"]
    for path in ("mlfabric", "mlfabric_overlap"):
        (pm, lm) = outs[path]
        assert abs(la - lm) < 1e-3, (path, la, lm)
        for a, b_ in zip(jax.tree.leaves(pa), jax.tree.leaves(pm)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b_, np.float32),
                                       rtol=3e-2, atol=3e-2,
                                       err_msg=path)
    print("MLFABRIC_PATH_OK")
""")


@pytest.mark.slow
def test_mlfabric_grad_path_matches_auto():
    """The scheduled-collective gradient path is numerically identical to
    GSPMD's automatic reduction after one optimizer step (8 fake devices,
    2x4 mesh, reduced stablelm)."""
    res = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd=_REPO_ROOT)
    assert "MLFABRIC_PATH_OK" in res.stdout, res.stderr[-2000:]


_COLLECTIVES_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AxisType
    from repro.dist.collectives import mlfabric_grad_reduce

    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    rng = np.random.default_rng(0)
    # one gradient slice per device on the leading dim (2 pods x 4 workers)
    grads = {
        "w1": jnp.asarray(rng.normal(size=(8, 33, 7)), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(8, 512)), jnp.float32),
        "bias": jnp.asarray(rng.normal(size=(8, 5)), jnp.float32),
        "big": jnp.asarray(rng.normal(size=(8, 3000)), jnp.float32),
    }
    ref = {k: np.mean(np.asarray(v), axis=0, keepdims=True)
           for k, v in grads.items()}

    def reduce_with(**kw):
        def body(g):
            return mlfabric_grad_reduce(g, intra_axis="data",
                                        inter_axis="pod", mean_over=8, **kw)
        specs = jax.tree.map(lambda _: P(("pod", "data")), grads)
        outs = jax.tree.map(lambda _: P(), grads)
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                                  out_specs=outs, check_vma=False))
        return jax.device_get(f(grads))

    cases = {
        "default": dict(),
        "tiny_buckets": dict(bucket_bytes=1024),
        "fifo": dict(shortest_first=False),
        "compressed": dict(compress_inter=True),
        "switch": dict(backend="switch"),
        "hierarchical": dict(backend="hierarchical"),
    }
    loose = ("compressed", "switch", "hierarchical")
    for name, kw in cases.items():
        got = reduce_with(**kw)
        tol = dict(rtol=5e-2, atol=5e-2) if name in loose \\
            else dict(rtol=1e-5, atol=1e-5)
        for k in grads:
            np.testing.assert_allclose(got[k], ref[k], err_msg=(name, k),
                                       **tol)
        print(name, "ok")
    print("COLLECTIVES_NUMERICS_OK")
""")


@pytest.mark.slow
def test_mlfabric_grad_reduce_matches_psum_mean():
    """Bucketed / shortest-first / int8-compressed hierarchical reduction
    equals a plain psum mean on a 2-pod x 4-worker host mesh."""
    res = subprocess.run([sys.executable, "-c", _COLLECTIVES_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd=_REPO_ROOT)
    assert "COLLECTIVES_NUMERICS_OK" in res.stdout, res.stderr[-2000:]


_PODS_F32_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.configs import get_config, get_shape
    from repro.data import SyntheticLM
    from repro.launch.steps import build_step
    from repro.models import transformer as tf
    from repro.optim.sgd import momentum_sgd_init

    mesh = jax.make_mesh((2, 2, 1), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    cfg = get_config("qwen2-0.5b").reduced()
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                                global_batch=4)
    params = tf.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    opt = momentum_sgd_init(params)
    batch = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=64,
                        seed=0).batch(0, 4)
    outs = {}
    for path in ("auto", "mlfabric"):
        b = build_step(cfg, shape, mesh, lr=0.1, grad_path=path)
        p2, o2, m = jax.jit(b.fn)(params, opt, batch)
        outs[path] = (float(m["loss"]), jax.device_get((p2, o2.history)))
    assert abs(outs["auto"][0] - outs["mlfabric"][0]) < 1e-5, outs
    for a, b_ in zip(jax.tree.leaves(outs["auto"][1]),
                     jax.tree.leaves(outs["mlfabric"][1])):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-6)
    print("PODS_F32_OK")
""")


@pytest.mark.slow
def test_mlfabric_step_matches_auto_across_pods_f32():
    """With f32 parameters the MLfabric step on a (pod=2, data=2, model=1)
    mesh (kernels called with every remaining axis manual) gives GSPMD's
    parameters and gradient to f32 rounding: what the bf16 comparison in
    ``chip_smoke.py --chips 4`` sees beyond that is rounding, not a lost or
    misplaced member."""
    res = subprocess.run([sys.executable, "-c", _PODS_F32_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd=_REPO_ROOT)
    assert "PODS_F32_OK" in res.stdout, res.stderr[-2000:]


def test_gradient_accumulation_matches_full_batch():
    """microbatches=4 gives the same loss/params as a single full batch."""
    import dataclasses
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_shape
    from repro.launch.steps import build_train_step
    from repro.models import transformer as tf
    from repro.optim.sgd import momentum_sgd_init

    mesh = make_host_mesh()
    cfg = get_config("stablelm-1.6b").reduced()
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                                global_batch=8)
    params = tf.init_params(jax.random.key(0), cfg)
    opt = momentum_sgd_init(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 64)),
                                   jnp.int32)}
    outs = {}
    for m in (1, 4):
        b = build_train_step(cfg, shape, mesh, lr=0.1, microbatches=m)
        p2, o2, metrics = b.jitted()(jax.device_get(params),
                                     jax.device_get(opt), batch)
        outs[m] = (jax.device_get(p2), float(metrics["loss"]))
    assert abs(outs[1][1] - outs[4][1]) < 1e-2, (outs[1][1], outs[4][1])
    for a, b_ in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[4][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   rtol=3e-2, atol=3e-2)
