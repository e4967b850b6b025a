"""exchange_ms: device time of the collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all) per step,
the union of their intervals on each chip, averaged over chips.  No
collective in the trace gives no value."""


def read(run):
    seconds = run.trace.collective_s()
    if seconds <= 0.0:
        return None
    return 1e3 * seconds / run.n_steps
