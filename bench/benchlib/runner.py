"""One run of one cell: set-up, the measured (or traced) window, then the
comparison with the reference, and the result line."""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from . import correct, flops, harness, peaks
from . import trace as trace_mod
from .cells import Cell, metric_reader


def use_compile_cache() -> str:
    """JAX's persistent cache, in the directory the program uses
    (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), holding
    every program however fast it compiled, so that only a checkout's first
    run compiles."""
    from repro.launch.compile_cache import use_compile_cache as program_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return program_cache()


class RunContext:
    """What a per-layer metric reader may read."""

    def __init__(self, cell: Cell, prep: harness.Prepared, reduced,
                 n_steps: int, device_kind: str):
        self.cell = cell
        self.config = cell.config
        self.mix = cell.mix
        self.chips = cell.chips
        self.trace = reduced
        self.n_steps = n_steps
        self.peak = peaks.peak(device_kind)
        self.tokens_per_step = prep.tokens_per_step
        self.leaf_sizes = [int(np.prod(x.shape))
                           for x in jax.tree.leaves(prep.params)]
        self.flops = flops


def _device(devices, peak_bytes=None, **extra) -> Dict:
    out = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if peak_bytes is not None:
        out["memory_peak_bytes"] = int(peak_bytes)
    out.update(extra)
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, *, wrap_step: Optional[Callable] = None,
        param_dtype=None, out=None, err=None) -> Optional[Dict]:
    out = out or sys.stdout
    err = err or sys.stderr
    prep = harness.prepare(cell, seed, devices, wrap_step=wrap_step,
                           param_dtype=param_dtype)
    prog_read = harness.first_steps(prep)
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.3f} s; compiled step: "
          f"{prep.built.memory_analysis}", file=err, flush=True)

    metrics: Dict = {}
    extra: Dict = {}
    result_extra: Dict = {}
    first = harness.FIRST_STEPS
    if not trace:
        times, window_s, bad = harness.window(prep, seconds, first)
        attempted = len(times)
        e2e = {"tokens_per_s": attempted * prep.tokens_per_step / window_s,
               "step_ms_p90": 1e3 * float(np.percentile(times, 90)),
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        slow = sorted(range(attempted), key=lambda i: -times[i])[:5]
        print(f"window {window_s:.3f} s, {attempted} steps, step ms "
              f"median {1e3 * float(np.median(times)):.3f}, slowest "
              f"{[(i, round(1e3 * times[i], 1)) for i in slow]}", file=err)
    else:
        n_steps = cell.mix["trace_steps"]
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            window_s, bad = harness.traced_window(prep, n_steps, logdir, first)
            reduced = trace_mod.load(logdir, devices)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        attempted = n_steps
        ctx = RunContext(cell, prep, reduced, n_steps,
                         devices[0].device_kind)
        for m in cell.per_layer:
            value = metric_reader(cell, m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra = {"busy_s": reduced.busy_s(), "window_s": reduced.window_s()}
        result_extra["breakdown"] = reduced.breakdown()
        print(f"traced {n_steps} steps in {window_s:.3f} s (host)",
              file=err)

    peak_bytes = harness.memory_peak(devices)
    print(f"peak_bytes_in_use {peak_bytes}", file=out, flush=True)
    host_batches = prep.host_batches
    ref_mod = prep.built.ref
    harness.release(prep)
    del prep

    ref_read = harness.reference(cell, ref_mod, seed, host_batches, devices[0])
    read = correct.readings(prog_read, ref_read)
    ok, compared = correct.judge(read, cell.limits)
    ok = ok and bad == 0
    print(f"worst leaves: grad {read['grad_worst']}, delta "
          f"{read['delta_worst']}; left out {read['left_out']}", file=err)
    print(f"losses program {prog_read['losses']} reference "
          f"{ref_read['losses']}", file=err)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=err)
    err.flush()
    result = {"correct": bool(ok), "attempted": attempted, "failed": bad,
              "metrics": metrics,
              "device": _device(devices, peak_bytes, **extra),
              **result_extra, "compared": compared}
    print(json.dumps(result), file=out, flush=True)
    return result
