"""Give each device operation of the traced step to a layer of the program.

Every instruction of the compiled step carries JAX's name stack in its
``op_name`` metadata, and the profiler trace names each device operation by
its instruction (``trace.instr_name``).  The program names its layers with
``jax.named_scope`` (``model``, ``optimizer``, ``metrics``, and
``exchange`` holding ``pack``, ``bucket<kk>`` with ``intra`` and ``inter``,
and ``unpack``); JAX puts the transformation around the scope, so the
forward reads ``jvp(model)``, the backward ``transpose(jvp(model))`` and
the remat recompute ``rematted_computation``.  A fusion carries the
``op_name`` of its root.

An instruction takes the first layer whose rule matches:

0. a collective (all-reduce, all-gather, reduce-scatter, collective-permute,
   all-to-all, as ``trace.is_collective`` reads its text): ``exchange``,
   whatever its ``op_name``;
1. ``op_name`` contains ``/optimizer/``: ``optimizer``;
2. contains ``/exchange/pack``: ``pack``; ``/exchange/unpack``: ``unpack``;
3. contains ``/exchange/``: ``exchange``;
4. contains ``rematted_computation``: ``recompute``;
5. contains ``transpose(jvp(model))``: ``backward``;
6. contains ``jvp(model)``: ``forward``;
7. anything else, and a device operation the compiled step does not name:
   ``other``.

So the layers partition the device's self time.  The rule is the
benchmark's: the program names its scopes, the benchmark decides what each
layer holds.

The readers take the optimized HLO text from the cell's step compiled once
more, after the traced window, as the harness compiles it
(``harness.compile_step``): JAX's persistent cache, which the program keys
on metadata, gives back the executable the run traced.  Where that compile
fails, the readers leave their metrics out of the line.
"""

from __future__ import annotations

import re
import sys
from typing import Dict, Optional

import jax

from . import harness, trace

LAYERS = ("forward", "backward", "recompute", "optimizer", "pack", "unpack",
          "exchange", "other")

# (layer, what its op_name contains), in the order they are tried
RULES = (
    ("optimizer", "/optimizer/"),
    ("pack", "/exchange/pack"),
    ("unpack", "/exchange/unpack"),
    ("exchange", "/exchange/"),
    ("recompute", "rematted_computation"),
    ("backward", "transpose(jvp(model))"),
    ("forward", "jvp(model)"),
)

_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def layer_of(op_name: str, collective: bool = False) -> str:
    if collective:
        return "exchange"
    for layer, needle in RULES:
        if needle in op_name:
            return layer
    return "other"


def _instructions(hlo_text: str):
    """(name, text before the metadata, op_name) of every instruction."""
    for line in hlo_text.splitlines():
        m = _LINE.match(line)
        if m:
            rest = m.group(2)
            op = _OP_NAME.search(rest)
            yield (m.group(1), rest[:op.start()] if op else rest,
                   op.group(1) if op else "")


def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` of every instruction in an HLO
    module's text; an instruction without metadata maps to ``""``."""
    return {name: op for name, _, op in _instructions(hlo_text)}


def instruction_layers(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: layer}`` by the rule above."""
    return {name: layer_of(op, trace.is_collective(head))
            for name, head, op in _instructions(hlo_text)}


def layer_seconds(reduced: trace.Reduced,
                  layers: Dict[str, str]) -> Dict[str, float]:
    """Device self seconds of each layer in the whole trace, mean over
    chips.  Every layer of ``LAYERS`` is present; they sum to the trace's
    total self time."""
    total = dict.fromkeys(LAYERS, 0.0)
    for dev in reduced.devices.values():
        for ev, t, _ in trace.self_times(dev.ops):
            total[layers.get(ev.name, "other")] += t
    n = len(reduced.devices)
    return {k: v / n * 1e-9 for k, v in total.items()}


_STEP_TEXT: Dict[str, Optional[str]] = {}


def step_text(run) -> Optional[str]:
    """The optimized HLO text of the run's compiled step, compiled once per
    cell and process; ``None`` where the step does not compile."""
    name = run.cell.name
    if name not in _STEP_TEXT:
        try:
            built = harness.compile_step(run.cell,
                                         jax.devices()[:run.chips])
            _STEP_TEXT[name] = built.compiled.as_text()
        except Exception as e:  # a metric left out, never a failed run
            print(f"scopes: no compiled step for {name}: {e!r}",
                  file=sys.stderr)
            _STEP_TEXT[name] = None
    return _STEP_TEXT[name]


def step_ms(run, *names: str) -> Optional[float]:
    """Device self time per step, in ms, of the layers ``names`` together,
    for a per-layer metric reader.  ``None`` where the compiled step gives
    none of them an instruction (a step without those scopes) or gives no
    text."""
    hlo_text = step_text(run)
    if not hlo_text:
        return None
    layers = instruction_layers(hlo_text)
    if not set(names) & set(layers.values()):
        return None
    seconds = layer_seconds(run.trace, layers)
    return 1e3 * sum(seconds[k] for k in names) / run.n_steps
