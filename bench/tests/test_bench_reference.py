"""The plain Qwen2 reference against the program's own training step, at a
tiny width on the CPU: in float32 they agree to rounding on both gradient
paths; with the step in bfloat16, or with the reference in fp8 put in the
program's place (the control), the readings grow by orders."""

import jax
import jax.numpy as jnp
import pytest
from conftest import tiny_cell

from benchlib import correct, harness

SEED = 2 ** 33 + 17          # above 32 bits: both key words in use
F32_LIMIT = 1e-4             # a few f32 roundings through two layers


def readings(cell, *, param_dtype=None, seed=SEED, precision=None):
    devices = jax.devices()[:cell.chips]
    prep = harness.prepare(cell, seed, devices, param_dtype=param_dtype)
    if precision is None:
        prog = harness.first_steps(prep)
    host_batches = prep.host_batches
    ref_mod = prep.built.ref
    harness.release(prep)
    ref = harness.reference(cell, ref_mod, seed, host_batches, devices[0])
    if precision is not None:
        prog = harness.reference(cell, ref_mod, seed, host_batches,
                                 devices[0], precision=precision)
    return correct.readings(prog, ref)


def f32_cell(**step):
    cell = tiny_cell(**step)
    cell.config["torch_dtype"] = "float32"
    return cell


@pytest.mark.parametrize("step", [
    dict(grad_path="auto"),
    dict(grad_path="mlfabric", compress_inter=False),
], ids=["auto", "mlfabric"])
def test_program_agrees_in_f32(step):
    r = readings(f32_cell(**step), param_dtype=jnp.float32)
    for name in correct.NUMBERS:
        assert r[name] < F32_LIMIT, (name, r)


def test_int8_wire_stays_near_in_f32():
    # the int8 wire rounds each gradient element to 1/254 of its block's
    # largest: the per-leaf norms move far less than that
    r = readings(f32_cell(grad_path="mlfabric", compress_inter=True),
                 param_dtype=jnp.float32)
    assert r["grad_gap"] < 1e-2 and r["loss_gap"] < F32_LIMIT, r


@pytest.mark.parametrize("step", [
    dict(grad_path="auto"),
    dict(grad_path="mlfabric", compress_inter=True),
], ids=["auto", "mlfabric-int8"])
def test_lower_precision_step_fails_f32_limits(step):
    cell = tiny_cell(**step)            # the program's bf16 parameters
    r = readings(cell)
    assert max(r[n] / F32_LIMIT for n in correct.NUMBERS) > 10, r


def test_control_reads_above_the_program():
    """The fp8 control against the bf16 program, same seed: the control
    reads higher on every number."""
    cell = tiny_cell()
    prog = readings(cell)
    ctrl = readings(cell, precision="fp8")
    for name in correct.NUMBERS:
        assert ctrl[name] > prog[name], (name, prog, ctrl)
