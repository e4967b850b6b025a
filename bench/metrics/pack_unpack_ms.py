"""pack_unpack_ms: device self time per step of the MLfabric path's flat
pack of the gradient and its unpack into leaves, the operations under
``exchange/pack`` and ``exchange/unpack`` (``benchlib/scopes.py``),
averaged over chips.  A step without those scopes gives no value."""

from benchlib import scopes


def read(run):
    return scopes.step_ms(run, "pack", "unpack")
