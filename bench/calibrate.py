"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 3] --out <directory>

In one process, with the cell's step compiled once:

* sound runs: for every seed, the program's first three steps (the same
  calls as a benchmark run) against the float32 reference;
* the control: for the first ``--control-seeds`` seeds, the reference
  computed in fp8 (e4m3, one scale per tensor; the precision below the
  configuration's bfloat16) put in the program's place;
* faults planted in the reference put in the program's place, on the same
  seeds: half of each batch left out (the mean taken over the rest), and on
  several chips the exchange left out (the first chip's rows alone).  A
  state left unchanged, or a leaf left unmoved, reads 1 by construction.

Each reading is one JSON line in ``<out>/<cell>.jsonl``; a summary of the
lower reading (largest sound) and the upper readings follows on stdout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def tpu_devices(n):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        return None
    return devices[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    sys.path.insert(0, BENCH_DIR)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchlib import cells, correct, harness, runner

    cell = cells.resolve(args.workload, ROOT)
    import jax

    devices = tpu_devices(cell.chips)
    if devices is None:
        print(f"calibrate: needs {cell.chips} TPU chips, found "
              f"{jax.devices()}", file=sys.stderr)
        return 3
    runner.use_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, cell.name + ".jsonl")
    built = harness.compile_step(cell, devices)
    print(f"compiled in {time.perf_counter() - T_START:.1f} s", flush=True)
    bpc = cell.mix["batch_per_chip"]
    rows = {"half_batch": lambda v: v[: v.shape[0] // 2]}
    if cell.chips > 1:
        rows["no_exchange"] = lambda v: v[:bpc]
    out = {"sound": [], "control": [], **{k: [] for k in rows}}

    def record(kind, seed, read, **extra):
        line = {"cell": cell.name, "kind": kind, "seed": seed,
                **{k: read[k] for k in correct.NUMBERS},
                "grad_worst": read["grad_worst"],
                "delta_worst": read["delta_worst"], **extra}
        out[kind].append(line)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        prep = harness.place(built, seed)
        prog = harness.first_steps(prep)
        host_batches = prep.host_batches
        harness.release(prep)
        del prep
        ref = harness.reference(cell, built.ref, seed, host_batches,
                                devices[0])
        read = correct.readings(prog, ref)
        record("sound", seed, read, losses=prog["losses"],
               ref_losses=ref["losses"], left_out=read["left_out"],
               seconds=time.perf_counter() - t)
        if n >= args.control_seeds:
            continue
        low = harness.reference(cell, built.ref, seed, host_batches,
                                devices[0], precision="fp8")
        record("control", seed, correct.readings(low, ref))
        for kind, pick in rows.items():
            bad = harness.reference(cell, built.ref, seed, host_batches,
                                    devices[0], rows_of=pick)
            record(kind, seed, correct.readings(bad, ref))

    print("summary", cell.name)
    for name in correct.NUMBERS:
        lower = max(r[name] for r in out["sound"])
        uppers = {k: min(r[name] for r in v) for k, v in out.items()
                  if k != "sound" and v}
        print(f"  {name}: lower {lower!r} upper {uppers}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
