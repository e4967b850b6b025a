"""backward_ms: device self time per step of the model's backward pass,
the operations under ``transpose(jvp(model))`` less the remat recompute
(``benchlib/scopes.py``), averaged over chips.  A step without the
``model`` scope gives no value."""

from benchlib import scopes


def read(run):
    return scopes.step_ms(run, "backward")
