"""exchange_exposed_ms: the part of ``exchange_ms`` during which no other
operation computes on that chip, per step, averaged over chips.  No
collective in the trace gives no value."""


def read(run):
    if run.trace.collective_s() <= 0.0:
        return None
    return 1e3 * run.trace.exposed_collective_s() / run.n_steps
