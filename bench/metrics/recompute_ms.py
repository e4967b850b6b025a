"""recompute_ms: device self time per step of the remat recompute, the
operations under ``rematted_computation`` (``benchlib/scopes.py``),
averaged over chips.  A step with no recompute gives no value."""

from benchlib import scopes


def read(run):
    return scopes.step_ms(run, "recompute")
