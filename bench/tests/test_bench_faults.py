"""A whole run, without the look for a chip, with the timed path broken
underneath: ``correct`` comes out false for each fault a training cell can
have.  Tiny widths on the CPU, the cells' own limits."""

import io
import json
import os
import subprocess
import sys
import time

import jax
import pytest
from conftest import BENCH, ROOT, tiny_cell

from benchlib import runner

LIMITS_1 = json.load(open(os.path.join(
    BENCH, "limits", "qwen2-0.5b.mlfabric-int8.1chip.json")))
LIMITS_4 = json.load(open(os.path.join(
    BENCH, "limits", "qwen2-0.5b.mlfabric-int8.4chip.json")))
SEED = 2 ** 35 + 5


def run(cell, wrap_step=None):
    out, err = io.StringIO(), io.StringIO()
    result = runner.run(cell, SEED, 0.5, False, jax.devices()[:cell.chips],
                        time.perf_counter(), wrap_step=wrap_step,
                        out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    return result


def state_unchanged(fn):
    def step(params, opt, batch):
        _, _, metrics = fn(params, opt, batch)
        return params, opt, metrics
    return step


def half_batch(fn):
    def step(params, opt, batch):
        return fn(params, opt, {k: v[: v.shape[0] // 2]
                                for k, v in batch.items()})
    return step


def leaf_unmoved(fn):
    """The update of one leaf (the MLP's down projection) is lost."""
    def step(params, opt, batch):
        new_p, new_o, metrics = fn(params, opt, batch)
        new_p["layers"]["mlp"]["down"] = params["layers"]["mlp"]["down"]
        new_o.history["layers"]["mlp"]["down"] = (
            opt.history["layers"]["mlp"]["down"])
        return new_p, new_o, metrics
    return step


@pytest.mark.parametrize("grad_path", ["mlfabric", "auto"])
@pytest.mark.parametrize("fault", [state_unchanged, half_batch, leaf_unmoved],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, grad_path):
    result = run(tiny_cell(limits=LIMITS_1, grad_path=grad_path),
                 wrap_step=fault)
    assert result["correct"] is False, result["compared"]
    assert list(result)[-1] == "compared"


NO_EXCHANGE = r"""
import io, json, sys, time
sys.path[:0] = [{bench!r}, {tests!r}, {src!r}]
import jax, jax.numpy as jnp
import repro.dist.collectives as coll
from conftest import tiny_cell
from benchlib import runner

def local_only(grads, layout, *, token=None, **kw):
    flat = coll.pack_leaves(jax.tree_util.tree_leaves(grads))
    vecs = [coll.bucket_slice(flat, layout, k)
            for k in range(len(layout.buckets))]
    return vecs, jnp.zeros((), jnp.float32) if token is None else token

if sys.argv[1] == "fault":
    coll.reduce_flat_buckets = local_only
cell = tiny_cell(chips=4, limits=json.loads(sys.argv[2]))
r = runner.run(cell, {seed}, 0.5, False, jax.devices()[:4],
               time.perf_counter(), out=io.StringIO(), err=io.StringIO())
print(json.dumps(r["compared"]), r["correct"])
"""


@pytest.mark.parametrize("mode", ["fault", "sound"])
def test_exchange_left_out_is_not_correct(mode):
    """On four devices, each chip keeps its own gradient: the exchange
    between chips is left out (run beside the same run with it in)."""
    code = NO_EXCHANGE.format(bench=BENCH, tests=os.path.join(BENCH, "tests"),
                              src=os.path.join(ROOT, "src"), seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code, mode,
                           json.dumps(LIMITS_4)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    compared, correct = proc.stdout.strip().splitlines()[-1].rsplit(" ", 1)
    grad = json.loads(compared)["grad_gap"]["value"]
    if mode == "fault":
        assert correct == "False", compared
    else:
        assert grad < 0.2, compared
