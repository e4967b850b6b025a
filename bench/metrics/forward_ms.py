"""forward_ms: device self time per step of the model's forward pass, the
operations under ``jvp(model)`` that are neither backward nor remat
recompute (``benchlib/scopes.py``), averaged over chips.  A step without
the ``model`` scope gives no value."""

from benchlib import scopes


def read(run):
    return scopes.step_ms(run, "forward")
