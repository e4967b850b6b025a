"""Benchmark of the MLfabric training step on TPU: one cell per process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, limits and per-layer metric readers are found
by name under ``bench/`` (``benchlib/cells.py``).  The run makes the
weights and batches from ``--seed``, compiles and warms the cell's step
(set-up), measures for ``--seconds`` (``--trace 0``: the end-to-end
metrics) or traces a few steps (``--trace 1``: the per-layer metrics),
then compares what the timed path produced with the plain float32
reference.  The last line of standard output is one JSON object.  Where JAX
finds no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH_DIR)
    from benchlib import cells

    cell = cells.resolve(args.workload, ROOT)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is not in {src}", file=sys.stderr)
        return 4
    sys.path.insert(0, src)

    from benchlib import runner
    runner.use_compile_cache()
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        devices[:cell.chips], T_START)
    return 0 if result is not None else 5


if __name__ == "__main__":
    raise SystemExit(main())
