"""Drive the system under test: the program's normal training step, built
by ``repro.launch.steps.build_step`` with its own shardings and donation.

``prepare`` makes one object (the compiled step, its parameters and
momentum made on the device from the seed, and the batches placed with the
step's batch sharding); ``first_steps`` drives it through the first three
steps, through the same call and feed as the window, and keeps what the
comparison needs; ``window`` and ``traced_window`` run it on.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import AxisType, SingleDeviceSharding

from . import correct
from .cells import Cell
from .seeds import seed_key
from .synthetic import SyntheticLM

FIRST_STEPS = 3          # steps the reference follows
MESH_AXES = ("pod", "data", "model")


@dataclasses.dataclass
class Built:
    """The cell's compiled step and what places its state."""
    cell: Cell
    mesh: Any
    compiled: Any
    make_params: Callable         # seed key -> the program's initial params
    init_opt: Callable            # params -> the program's momentum state
    batch_sharding: Any
    ref: Any                      # the family's reference module
    prog: Any                     # the family's program adapter
    memory_analysis: Any = None


@dataclasses.dataclass
class Prepared:
    built: Built
    seed: int
    params: Any
    opt: Any
    batches: List[Any]            # on the device, in the step's sharding
    host_batches: List[Dict]

    @property
    def cell(self) -> Cell:
        return self.built.cell

    @property
    def global_batch(self) -> int:
        return self.cell.mix["batch_per_chip"] * self.cell.chips

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * self.cell.mix["seq_len"]


def make_mesh(cell: Cell, devices):
    shape = cell.mix["mesh"][str(cell.chips)]
    return jax.make_mesh(tuple(shape[a] for a in MESH_AXES), MESH_AXES,
                         axis_types=(AxisType.Auto,) * 3,
                         devices=devices[:cell.chips])


def build(cell: Cell, mesh, program_cfg):
    """The program's step for this cell's mix (``launch/steps.build_step``)."""
    from repro.configs.shapes import ShapeConfig
    from repro.launch.steps import build_step

    mix = cell.mix
    shape = ShapeConfig(cell.name, mix["seq_len"],
                        mix["batch_per_chip"] * cell.chips, "train")
    kw = dict(mix["step"])
    return build_step(program_cfg, shape, mesh, grad_path=kw.pop("grad_path"),
                      **kw)


def compile_step(cell: Cell, devices, *, wrap_step: Optional[Callable] = None,
                 param_dtype=None) -> Built:
    """Build and compile the cell's step.  ``wrap_step`` (tests only)
    replaces the step's function, to plant a fault underneath the timed
    path; ``param_dtype`` (tests only) holds the parameters in another
    dtype."""
    from repro.optim.sgd import momentum_sgd_init

    ref = cell.module(cell.family)
    prog = cell.module(cell.family + "_program")
    mesh = make_mesh(cell, devices)
    bundle = build(cell, mesh, prog.program_config(cell.config))
    fn = wrap_step(bundle.fn) if wrap_step is not None else bundle.fn
    param_sh, opt_sh, batch_sh = bundle.in_shardings
    abstract = bundle.args[0]
    if param_dtype is not None:
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, param_dtype), abstract)
    cfg = cell.config
    make_params = jax.jit(
        lambda key: prog.to_program(ref.init_weights(cfg, key), abstract),
        out_shardings=param_sh)
    init_opt = jax.jit(momentum_sgd_init, out_shardings=opt_sh)
    abstract_opt = jax.eval_shape(momentum_sgd_init, abstract)
    jitted = jax.jit(fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings,
                     donate_argnums=bundle.donate_argnums)
    compiled = jitted.lower(abstract, abstract_opt, bundle.args[2]).compile()
    return Built(cell=cell, mesh=mesh, compiled=compiled,
                 make_params=make_params, init_opt=init_opt,
                 batch_sharding=batch_sh, ref=ref, prog=prog,
                 memory_analysis=compiled.memory_analysis())


def place(built: Built, seed: int) -> Prepared:
    """Parameters and momentum made on the device from the seed, each in
    one jitted call; the mix's batches made from the seed and placed with
    the step's batch sharding."""
    cell, cfg, mix = built.cell, built.cell.config, built.cell.mix
    params = built.make_params(seed_key(seed))
    opt = built.init_opt(params)
    src = SyntheticLM(vocab_size=cfg["vocab_size"], seq_len=mix["seq_len"],
                      seed=seed, structure=mix["structure"])
    gb = mix["batch_per_chip"] * cell.chips
    host_batches = [src.batch(i, gb) for i in range(mix["n_batches"])]
    batches = [jax.device_put(b, built.batch_sharding) for b in host_batches]
    return Prepared(built=built, seed=seed, params=params, opt=opt,
                    batches=batches, host_batches=host_batches)


def prepare(cell: Cell, seed: int, devices, **kw) -> Prepared:
    return place(compile_step(cell, devices, **kw), seed)


def step(p: Prepared, i: int):
    """One step of the window's own call and feed: batch ``i`` cycles
    through the batches placed at set-up.  Blocks on the outputs and
    returns the loss, as a loop that reads the loss every step does."""
    p.params, p.opt, metrics = p.built.compiled(p.params, p.opt,
                                          p.batches[i % len(p.batches)])
    return float(metrics["loss"])


def first_steps(p: Prepared) -> Dict:
    """Steps 1-3 of the run, through ``step``.  Keeps the losses, the
    per-leaf norms of the first gradient as the optimizer got it (from the
    momentum state after one step, ``-history / lr``) and of the
    parameters' change after the three steps (before step 4 takes them)."""
    lr = p.cell.mix["step"]["lr"]
    losses = [step(p, 0)]
    prog = p.built.prog
    grad = correct.host(correct.leaf_norms(prog.from_program(p.opt.history)))
    grad = {k: v / lr for k, v in grad.items()}
    for i in range(1, FIRST_STEPS):
        losses.append(step(p, i))
    start = p.built.make_params(seed_key(p.seed))
    delta = correct.diff_norms(prog.from_program(p.params),
                               prog.from_program(start))
    del start
    return {"losses": losses, "grad": grad, "delta": delta}


def window(p: Prepared, seconds: float, first: int = FIRST_STEPS):
    """Run steps for ``seconds`` of wall time, from step ``first`` on.
    Returns the per-step wall times, the window's length and the number of
    non-finite losses."""
    times: List[float] = []
    bad = 0
    i = first
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter()
        loss = step(p, i)
        now = time.perf_counter()
        times.append(now - t)
        bad += not np.isfinite(loss)
        i += 1
        if now - t0 >= seconds:
            return times, now - t0, bad


def traced_window(p: Prepared, n_steps: int, logdir: str, first: int):
    """``n_steps`` steps under the profiler, with the benchmark's host
    spans: ``window`` around them all, and per step ``feed`` (choosing the
    placed batch), ``dispatch`` (the call) and ``block`` (waiting for the
    outputs).  Returns the window's host seconds and the bad-loss count."""
    bad = 0
    with jax.profiler.trace(logdir):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            for i in range(first, first + n_steps):
                with jax.profiler.StepTraceAnnotation("train", step_num=i):
                    with jax.profiler.TraceAnnotation("feed"):
                        batch = p.batches[i % len(p.batches)]
                    with jax.profiler.TraceAnnotation("dispatch"):
                        p.params, p.opt, m = p.built.compiled(p.params, p.opt, batch)
                    with jax.profiler.TraceAnnotation("block"):
                        bad += not np.isfinite(float(m["loss"]))
        seconds = time.perf_counter() - t0
    return seconds, bad


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [x for x in peaks if x is not None]
    return max(peaks) if peaks else None


def release(p: Prepared) -> None:
    """Free the program's state before the reference runs."""
    p.params = p.opt = None
    p.batches = []
    gc.collect()


def reference(cell: Cell, ref, seed: int, host_batches: List[Dict], device,
              *, precision: str = "f32", rows_of=None) -> Dict:
    """The plain reference over the same first batches, from the same
    weights, on one device.  ``rows_of`` (faults planted in the reference)
    picks the rows of each batch it sees."""
    cfg, mix = cell.config, cell.mix
    batches = host_batches[:FIRST_STEPS]
    if rows_of is not None:
        batches = [{k: rows_of(v) for k, v in b.items()} for b in batches]
    init = jax.jit(lambda key: ref.init_weights(cfg, key),
                   out_shardings=SingleDeviceSharding(device))
    start = init(seed_key(seed))
    out = ref.train_steps(cfg, start, batches, lr=mix["step"]["lr"],
                          gamma=mix["step"]["gamma"],
                          rows=mix["batch_per_chip"], precision=precision,
                          device=device)
    start = init(seed_key(seed))
    res = {"losses": out["losses"],
           "grad": correct.host(correct.leaf_norms(out["grad"])),
           "delta": correct.diff_norms(out["params"], start)}
    del out, start
    gc.collect()
    return res
