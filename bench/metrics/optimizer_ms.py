"""optimizer_ms: device self time per step of the momentum update, the
operations under the ``optimizer`` scope (``benchlib/scopes.py``),
averaged over chips.  A step without that scope gives no value."""

from benchlib import scopes


def read(run):
    return scopes.step_ms(run, "optimizer")
