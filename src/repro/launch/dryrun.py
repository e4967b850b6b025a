import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline inputs.

This is the proof that the distribution config is coherent without real
hardware (assignment: MULTI-POD DRY-RUN).  The two XLA_FLAGS lines above
MUST run before any other import — jax locks the device count on first init.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
        --shape train_4k [--multipod] [--out runs/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod]
"""

import argparse
import json
import re
import sys
import time
import traceback

import jax

from repro.configs import SHAPES, applicable, get_config, get_shape, list_configs
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.obs.report import roofline_attribution

# TPU v5e hardware constants (roofline targets; DESIGN.md §6)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\b")
_TYPE_RE = re.compile(r"\b([a-z]+\d+)\[([0-9,]*)\]")
_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
          "pred": 1, "f8": 1}


def _type_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str):
    """Sum operand bytes of every collective op in the (post-SPMD) HLO.

    Per-op convention: bytes = sum of operand tensor sizes (the data a
    device contributes to the collective); the per-category split is
    returned for the §Perf analysis.
    """
    per_kind = {}
    total = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None or "%" not in line or "=" not in line:
            continue
        kind = m.group(1)
        if f" {kind}(" not in line and f"{kind}-start(" not in line \
                and f"{kind}(" not in line:
            continue
        types = _TYPE_RE.findall(line)
        if not types:
            continue
        rhs = line.split("=", 1)[1]
        rhs_types = _TYPE_RE.findall(rhs.split("(", 1)[1]) if "(" in rhs else []
        use = rhs_types if rhs_types else types[1:]
        if not use:  # fall back to the result type
            use = types[:1]
        nbytes = sum(_type_bytes(t, d) for t, d in use)
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
        total += nbytes
    return total, per_kind


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: str = "runs/dryrun", save_hlo: bool = False,
             step_kwargs=None) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    bundle = build_step(cfg, shape, mesh, **(step_kwargs or {}))
    lowered = bundle.lower()
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll_total, coll_kinds = collective_bytes(hlo)

    # loop-aware (trip-count-multiplied) collective bytes + dot FLOPs:
    # cost_analysis counts while bodies ONCE (verified vs analytic 6ND), so
    # the scan-over-layers structure would otherwise undercount ~n_layers x.
    from repro.launch.hlo_analysis import analyze as hlo_analyze
    la_kinds, la_flops = hlo_analyze(hlo)
    la_total = sum(la_kinds.values())

    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        # per-device (post-SPMD partitioned module) numbers.  *_raw come
        # from cost_analysis / a flat text scan (loop bodies counted once);
        # the loop-aware numbers multiply while-body contributions by their
        # known_trip_count and are what the roofline uses.
        "flops_per_device_raw": flops_dev,
        "flops_per_device": la_flops if la_flops > 0 else flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_raw": coll_total,
        "collective_bytes_per_device": la_total if la_total > 0 else coll_total,
        "collective_by_kind": la_kinds if la_kinds else coll_kinds,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        # roofline terms (seconds; per-device == total/(chips*peak)).
        # HBM bytes from cost_analysis share the loops-counted-once issue;
        # scale by the loop-amplification factor observed on FLOPs.
        "t_compute": (la_flops if la_flops > 0 else flops_dev) / PEAK_FLOPS,
        "t_memory": (bytes_dev * (la_flops / flops_dev
                                  if la_flops > 0 and flops_dev > 0 else 1.0)
                     ) / HBM_BW,
        "t_collective": (la_total if la_total > 0 else coll_total) / ICI_BW,
    }
    # shared attribution dialect (repro.obs.report): same dominant-term
    # convention and phase names as the cluster-level BottleneckReport
    roofline = roofline_attribution(result["t_compute"], result["t_memory"],
                                    result["t_collective"])
    result["bottleneck"] = roofline["bottleneck"]
    result["bottleneck_share"] = roofline["share"]

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{result['mesh'].replace('x', '-')}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=2)
        if save_hlo:
            with open(os.path.join(out_dir, tag + ".hlo.txt"), "w") as f:
                f.write(hlo)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="architecture id (see --list)")
    ap.add_argument("--shape", help="input shape name")
    ap.add_argument("--multipod", action="store_true",
                    help="2x16x16 multi-pod mesh (default: 16x16)")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantized KV cache for decode cells")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation slices for train cells")
    args = ap.parse_args()

    if args.list:
        for a in list_configs():
            print(a)
        return 0

    cells = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in list_configs() for s in SHAPES])
    failures = 0
    for arch, shape in cells:
        try:
            kw = {}
            if args.kv_int8 and SHAPES[shape].kind == "decode":
                kw["kv_int8"] = True
            if args.microbatches > 1 and SHAPES[shape].kind == "train":
                kw["microbatches"] = args.microbatches
            res = run_cell(arch, shape, multi_pod=args.multipod,
                           out_dir=args.out, save_hlo=args.save_hlo,
                           step_kwargs=kw)
        except Exception:
            traceback.print_exc()
            res = {"arch": arch, "shape": shape, "status": "FAILED"}
            failures += 1
        line = (f"{res['arch']:24s} {res['shape']:12s} {res['status']:8s}")
        if res["status"] == "ok":
            line += (f" compile={res['compile_s']:7.1f}s"
                     f" flops/dev={res['flops_per_device']:.3e}"
                     f" coll/dev={res['collective_bytes_per_device']:.3e}"
                     f" peakmem={res['memory']['peak_bytes']/1e9:6.2f}GB"
                     f" bound={res['bottleneck']}")
        print(line, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
