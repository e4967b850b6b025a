"""The layer rule (``benchlib/scopes.py``) on a hand-built HLO text and
trace: the layers partition the device's self time, each rule takes
precedence in its order, and a reader of an empty layer gives no value.
The step's text comes from compiling the cell's step once more."""

import types

import numpy as np
import pytest

from benchlib import cells, harness, runner, scopes, trace
from benchlib.trace import DeviceOps, Event, Reduced
from conftest import tiny_cell

HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jvp(model)/mul"}
}

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(model)/while/body/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(model))/while/body/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(model))/while/body/closed_call/checkpoint/rematted_computation/dot_general"}
  %all-reduce.4 = f32[8]{0} all-reduce(%fusion.3), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(step)/transpose(jvp(model))/dot_general"}
  %fusion.5 = f32[8]{0} fusion(%all-reduce.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/exchange/pack/concatenate"}
  %quantize_op.6 = (s8[8]{0}, f32[1]{0}) custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/exchange/bucket00/inter/jit(quantize_op)/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/exchange/unpack/slice"}
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/optimizer/transpose(jvp(model))/exchange/pack/add"}
  %copy.10 = f32[8]{0} copy(%fusion.8)
  ROOT %fusion.11 = f32[8]{0} fusion(%copy.10), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/metrics/sqrt"}
}
"""

LAYER = {"fusion.1": "forward", "fusion.2": "backward",
         "fusion.3": "recompute", "all-reduce.4": "exchange",
         "fusion.5": "pack", "quantize_op.6": "exchange",
         "fusion.7": "unpack", "fusion.8": "optimizer", "copy.10": "other",
         "fusion.11": "other", "multiply.9": "forward", "p": "other",
         "param_0.1": "other"}


def ev(name, start, end, collective=False):
    return Event(name, float(start), float(end), collective)


def chip(shift=0, scale=1):
    s, k = shift, scale
    b = s + 50 * k
    return DeviceOps([
        ev("while.20", s, s + 40 * k),           # not in the module: other
        ev("fusion.1", s, s + 10 * k),
        ev("fusion.2", s + 10 * k, s + 25 * k),
        ev("fusion.3", s + 25 * k, s + 30 * k),
        ev("all-reduce.4", b, b + 20, True),
        ev("fusion.5", b + 20, b + 23),
        ev("quantize_op.6", b + 23, b + 30),
        ev("fusion.7", b + 30, b + 32),
        ev("fusion.8", b + 32, b + 40),
        ev("copy.10", b + 40, b + 41),
        ev("fusion.11", b + 41, b + 42),
        ev("fusion.1", b + 50, b + 60),
    ])


@pytest.fixture
def reduced():
    return Reduced({0: chip(), 1: chip(shift=500, scale=2)},
                   [("window", 0.0, 200.0)])


@pytest.fixture
def compiles(monkeypatch):
    """Stands in for ``harness.compile_step``: the cell's step text is its
    name's entry in ``texts``; ``calls`` counts the compiles."""
    texts, calls = {}, []

    def compile_step(cell, devices):
        calls.append(cell.name)
        if texts[cell.name] is None:
            raise RuntimeError("does not compile")
        return types.SimpleNamespace(
            compiled=types.SimpleNamespace(as_text=lambda: texts[cell.name]))

    monkeypatch.setattr(scopes, "_STEP_TEXT", {})
    monkeypatch.setattr(scopes.harness, "compile_step", compile_step)
    return texts, calls


def fake_run(reduced, compiles, hlo_text=HLO, name="cell"):
    compiles[0][name] = hlo_text
    return types.SimpleNamespace(trace=reduced, n_steps=2, chips=1,
                                 cell=types.SimpleNamespace(name=name))


def test_each_instruction_gets_its_layer():
    assert scopes.instruction_layers(HLO) == LAYER
    assert scopes.op_names(HLO)["fusion.11"] == "jit(step)/metrics/sqrt"
    assert scopes.op_names(HLO)["copy.10"] == ""


@pytest.mark.parametrize("op_name,collective,layer", [
    # a collective is the exchange whatever its op_name
    ("jit(step)/transpose(jvp(model))/dot_general", True, "exchange"),
    ("", True, "exchange"),
    # optimizer before pack, unpack, exchange and the model
    ("jit(s)/exchange/pack/optimizer/x", False, "optimizer"),
    ("jit(s)/optimizer/exchange/unpack/x", False, "optimizer"),
    ("jit(s)/optimizer/transpose(jvp(model))/x", False, "optimizer"),
    # pack and unpack before the rest of the exchange
    ("jit(s)/exchange/unpack/x", False, "unpack"),
    ("jit(s)/exchange/bucket03/intra/psum", False, "exchange"),
    ("jit(s)/exchange/bucket03/x/rematted_computation", False, "exchange"),
    # recompute before backward, backward before forward
    ("jit(s)/transpose(jvp(model))/checkpoint/rematted_computation/x",
     False, "recompute"),
    ("jit(s)/transpose(jvp(model))/while/body/x", False, "backward"),
    ("jit(s)/jvp(model)/while/body/x", False, "forward"),
    # a scope elsewhere in the stack does not count
    ("jit(s)/model/x", False, "other"),
    ("jit(s)/exchange", False, "other"),
    ("", False, "other"),
])
def test_rule_order(op_name, collective, layer):
    assert scopes.layer_of(op_name, collective) == layer


def test_collective_by_instruction_text_not_name():
    text = ('  %fusion.3 = f32[8]{0} fusion(%all-reduce.2), kind=kLoop, '
            'calls=%f, metadata={op_name="jit(s)/jvp(model)/add"}\n'
            '  %all-gather-start.4 = (f32[8]{0}, f32[16]{0}) '
            'all-gather-start(%fusion.3), dimensions={0}, '
            'metadata={op_name="jit(s)/jvp(model)/add"}\n')
    assert scopes.instruction_layers(text) == {
        "fusion.3": "forward", "all-gather-start.4": "exchange"}


def test_layers_partition_self_time(reduced):
    seconds = scopes.layer_seconds(reduced, scopes.instruction_layers(HLO))
    assert set(seconds) == set(scopes.LAYERS)
    # every event's self time, mean over the two chips
    total = sum(t for d in reduced.devices.values()
                for _, t, _ in trace.self_times(d.ops)) / 2 * 1e-9
    assert sum(seconds.values()) == pytest.approx(total, rel=1e-12)
    # chip 0: forward 10 + 10, backward 15, recompute 5; chip 1 doubles
    # the scan's work (forward 20 + 10)
    assert seconds["forward"] == pytest.approx((20 + 30) / 2 * 1e-9)
    assert seconds["backward"] == pytest.approx((15 + 30) / 2 * 1e-9)
    assert seconds["recompute"] == pytest.approx((5 + 10) / 2 * 1e-9)
    assert seconds["exchange"] == pytest.approx(27e-9)
    assert seconds["pack"] == pytest.approx(3e-9)
    assert seconds["unpack"] == pytest.approx(2e-9)
    assert seconds["optimizer"] == pytest.approx(8e-9)
    # the while's self time (chip 0: 40 - 30; chip 1: 80 - 60), the copy
    # without metadata and the metrics fusion
    assert seconds["other"] == pytest.approx((10 + 20) / 2 * 1e-9 + 2e-9)


READERS = ("forward_ms", "backward_ms", "recompute_ms", "optimizer_ms",
           "pack_unpack_ms")


def test_reader_values_and_empty_layers(reduced, compiles):
    run = fake_run(reduced, compiles)
    read = {m: cells.load_module(f"{cells.BENCH_DIR}/metrics/{m}.py").read
            for m in READERS}
    assert read["forward_ms"](run) == pytest.approx(1e3 * 25e-9 / 2)
    assert read["pack_unpack_ms"](run) == pytest.approx(1e3 * 5e-9 / 2)
    # a step without the scopes, or one that does not compile: no value,
    # not 0
    bare = HLO.replace("model", "m").replace("optimizer", "o").replace(
        "exchange/pack", "e/p").replace("exchange/unpack", "e/u").replace(
        "rematted_computation", "r")
    for name, f in read.items():
        assert f(fake_run(reduced, compiles, bare, "bare")) is None, name
        assert f(fake_run(reduced, compiles, None, "broken")) is None, name
    assert scopes.step_ms(run, "forward") == read["forward_ms"](run)


def test_step_compiled_once_per_cell(reduced, compiles):
    run = fake_run(reduced, compiles)
    broken = fake_run(reduced, compiles, None, "broken")
    for m in READERS:
        reader = cells.load_module(f"{cells.BENCH_DIR}/metrics/{m}.py")
        assert reader.read(run) is not None
        assert reader.read(broken) is None
    assert compiles[1] == ["cell", "broken"]


def test_step_text_is_the_harness_step(monkeypatch):
    """On the CPU, the step the readers compile names its instructions as
    the step the harness compiles for the run does, with the same
    ``op_name`` each (the source locations differ with the caller), and
    names every layer of the MLfabric path."""
    import jax

    monkeypatch.setattr(scopes, "_STEP_TEXT", {})
    cell = tiny_cell()
    run = types.SimpleNamespace(cell=cell, chips=1)
    text = scopes.step_text(run)
    built = harness.compile_step(cell, jax.devices()[:1])
    assert scopes.op_names(text) == scopes.op_names(built.compiled.as_text())
    layers = set(scopes.instruction_layers(text).values())
    assert {"forward", "backward", "recompute", "optimizer", "pack",
            "unpack", "exchange"} <= layers


def test_existing_readers_ignore_the_hlo_text(reduced, compiles):
    """The new readers leave what the existing readers read unchanged."""
    cell = cells.resolve("qwen2-0.5b.mlfabric-int8.4chip", cells.ROOT)
    compiles[0][cell.name] = HLO
    prep = types.SimpleNamespace(tokens_per_step=8 * 2048,
                                 params={"w": np.zeros((4, 1024))})
    ctx = runner.RunContext(cell, prep, reduced, 2, "TPU v5 lite")
    names = ("mfu", "idle_share", "int8_wire_roofline", "exchange_ms",
             "exchange_exposed_ms")
    before = {m: cells.metric_reader(cell, m).read(ctx) for m in names}
    scoped = {m: cells.metric_reader(cell, m).read(ctx) for m in READERS}
    after = {m: cells.metric_reader(cell, m).read(ctx) for m in names}
    assert before == after
    assert all(v is not None for v in before.values())
    assert all(v is not None for v in scoped.values())
