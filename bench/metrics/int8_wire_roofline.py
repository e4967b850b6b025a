"""int8_wire_roofline: the least time the int8 wire stage could take
(its HBM bytes per step, ``flops.int8_wire_bytes`` over the step's buckets,
at the chip's HBM bandwidth) over the device time of the kernels that do
that work: the ``quantize_op`` and ``dequant_aggregate_op`` custom calls.
The stage moves bytes and does next to no arithmetic, so bandwidth bounds
it.  Nothing to read (no such kernel ran) gives no value."""

KERNELS = r"(quantize_op|dequant_aggregate_op)(\.\d+)?$"


def read(run):
    seconds = run.trace.op_seconds(KERNELS) / run.n_steps
    if seconds <= 0.0:
        return None
    step = run.mix["step"]
    buckets = run.flops.plan_buckets(run.leaf_sizes, step["bucket_bytes"])
    pods = run.mix["mesh"][str(run.chips)]["pod"]
    need = run.flops.int8_wire_bytes(buckets, pods)["total"]
    return 100.0 * need / run.peak.hbm_bytes / seconds
