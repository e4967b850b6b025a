"""The train step names its layers on the device.

Both gradient paths are compiled on the CPU at reduced widths, on one
device and on four virtual devices with the (pod 2, data 2, model 1) mesh
and the int8 inter-pod wire.  Every instruction of the optimized HLO
carries JAX's name stack in its ``op_name``; the benchmark gives each to a
layer by the rule of ``bench/benchlib/scopes.py``.  These tests hold the
program's scopes (``repro.scopes``) to what that rule reads.
"""

import inspect
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from benchlib import scopes as bench_scopes  # noqa: E402

from repro import scopes  # noqa: E402
from repro.dist.collectives import (mlfabric_grad_reduce,  # noqa: E402
                                    plan_reduce, reduce_flat_buckets)

ARCH = "qwen2-0.5b"
BUCKET_BYTES = 65536
SEQ = 64
PATHS = ("auto", "mlfabric")

_COMPILE = textwrap.dedent("""
    import os, sys
    ndev = int(sys.argv[1])
    if ndev > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ndev}")
    sys.path.insert(0, "src")
    import dataclasses, json, jax
    from jax.sharding import AxisType
    from repro.configs import get_config, get_shape
    from repro.launch.steps import build_step

    mesh = jax.make_mesh((1, 1, 1) if ndev == 1 else (2, 2, 1),
                         ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3,
                         devices=jax.devices()[:ndev])
    cfg = get_config(%(arch)r).reduced()
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=%(seq)d,
                                global_batch=2 * ndev)
    out = {}
    for path in %(paths)r:
        kw = dict(grad_path=path, lr=0.1)
        if path == "mlfabric":
            kw.update(compress_inter=True, bucket_bytes=%(bucket)d)
        out[path] = build_step(cfg, shape, mesh, **kw).lower().compile(
            ).as_text()
    print(json.dumps(out))
""" % dict(arch=ARCH, seq=SEQ, paths=PATHS, bucket=BUCKET_BYTES))


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def compiled(request):
    """{path: optimized HLO text} of both train paths, and the device count."""
    ndev = request.param
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", _COMPILE, str(ndev)],
                         capture_output=True, text=True, timeout=600,
                         cwd=ROOT, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    return ndev, json.loads(res.stdout.strip().splitlines()[-1])


def _layout():
    from repro.configs import get_config
    from repro.models import api
    cfg = get_config(ARCH).reduced()
    return plan_reduce(api.params_specs(cfg), bucket_bytes=BUCKET_BYTES)


def _opcodes(text):
    """{instruction name: opcode} of every instruction."""
    return dict(re.findall(
        r"^\s*(?:ROOT\s+)?%(\S+) = [^=]*?\s([a-z][\w\-]*)\(", text, re.M))


def test_scope_names_are_what_the_benchmark_reads():
    assert scopes.SCOPES == ("model", "optimizer", "metrics", "exchange",
                             "pack", "unpack", "intra", "inter",
                             "bucket{:02d}")
    needles = dict((layer, needle) for layer, needle in bench_scopes.RULES)
    assert needles == {
        "optimizer": f"/{scopes.OPTIMIZER}/",
        "pack": f"/{scopes.EXCHANGE}/{scopes.PACK}",
        "unpack": f"/{scopes.EXCHANGE}/{scopes.UNPACK}",
        "exchange": f"/{scopes.EXCHANGE}/",
        "recompute": "rematted_computation",
        "backward": f"transpose(jvp({scopes.MODEL}))",
        "forward": f"jvp({scopes.MODEL})",
    }
    assert scopes.bucket(3) == "bucket03"
    assert scopes.bucket(123) == "bucket123"


def test_bucket_reduction_takes_no_tracer():
    assert "tracer" not in inspect.signature(reduce_flat_buckets).parameters
    assert "tracer" not in inspect.signature(mlfabric_grad_reduce).parameters


@pytest.mark.parametrize("path", PATHS)
def test_matmuls_belong_to_the_model(compiled, path):
    _, texts = compiled
    layers = bench_scopes.instruction_layers(texts[path])
    dots = [n for n, code in _opcodes(texts[path]).items()
            if code in ("dot", "convolution")]
    assert dots
    for name in dots:
        assert layers[name] in ("forward", "backward", "recompute"), name
    found = {layers[n] for n in dots}
    assert found == {"forward", "backward", "recompute"}


@pytest.mark.parametrize("path", PATHS)
def test_momentum_update_is_the_optimizer(compiled, path):
    _, texts = compiled
    ops = bench_scopes.op_names(texts[path])
    prims = {op.rsplit("/", 1)[-1] for op in ops.values()
             if f"/{scopes.OPTIMIZER}/" in op}
    # h' = -lr * g + gamma * h; w' = w + h'
    assert {"mul", "add"} <= prims
    layers = bench_scopes.instruction_layers(texts[path])
    assert "optimizer" in layers.values()
    if path == "auto":
        assert any(f"/{scopes.METRICS}/" in op for op in ops.values())


def test_exchange_scopes_follow_the_bucket_plan(compiled):
    ndev, texts = compiled
    text = texts["mlfabric"]
    layers = bench_scopes.instruction_layers(text)
    ops = bench_scopes.op_names(text)
    assert {"pack", "unpack", "exchange"} <= set(layers.values())
    layout = _layout()
    found = sorted({int(m.group(1)) for op in ops.values()
                    for m in [re.search(r"/exchange/bucket(\d+)/", op)] if m})
    assert found == list(range(len(layout.buckets)))
    assert all(re.search(r"/exchange/bucket\d\d/", op)
               for op in ops.values() if "/exchange/bucket" in op)
    if ndev == 1:
        return
    # on the pod mesh each bucket's int8 wire is gathered under its own
    # scope, at its own size: rows of 1024 lanes holding the bucket padded
    gathers = re.findall(r"= s8\[2,(\d+),1024\][^\n]* all-gather\("
                         r"[^\n]*op_name=\"([^\"]*)\"", text)
    for k, size in enumerate(layout.bucket_sizes):
        scope = f"/{scopes.EXCHANGE}/{scopes.bucket(k)}/{scopes.INTER}/"
        rows = [int(r) for r, op in gathers if scope in op]
        assert rows == [-(-size // 1024)], (k, size, rows)
    assert any(f"/{scopes.INTRA}/" in op for op in ops.values())


def test_auto_path_has_no_exchange_scope(compiled):
    _, texts = compiled
    layers = bench_scopes.instruction_layers(texts["auto"])
    assert not {"pack", "unpack"} & set(layers.values())
    assert not any(f"/{scopes.EXCHANGE}/" in op
                   for op in bench_scopes.op_names(texts["auto"]).values())
