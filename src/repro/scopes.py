"""Names of the train step's layers on the device.

The train step (``launch/steps.py``) and the gradient exchange
(``dist/collectives.py``) wrap their work in ``jax.named_scope`` blocks of
these names.  A scope adds metadata, not instructions: every instruction of
the compiled step carries JAX's name stack in its ``op_name``, with the
scope under the transformation that made it (the forward as
``jvp(model)/...``, the backward as ``transpose(jvp(model))/...``, the
remat recompute under ``rematted_computation``).  A profiler trace names
device operations by the same instructions, so device time can be given to
the layer that owns it.

The stack, outermost first::

    model          the loss, on every gradient path
    optimizer      the momentum update
    metrics        the step's metrics (grad norm, loss mean)
    exchange       the MLfabric gradient reduction
      pack           the flat pack of every leaf
      bucket<kk>     one bucket's exchange, ``kk`` its index in issue order
        intra          the intra-pod sum
        inter          the inter-pod aggregation
      unpack         the reduced buckets carved back into leaves
"""

MODEL = "model"
OPTIMIZER = "optimizer"
METRICS = "metrics"
EXCHANGE = "exchange"
PACK = "pack"
UNPACK = "unpack"
INTRA = "intra"
INTER = "inter"
BUCKET = "bucket{:02d}"

SCOPES = (MODEL, OPTIMIZER, METRICS, EXCHANGE, PACK, UNPACK, INTRA, INTER,
          BUCKET)


def bucket(k: int) -> str:
    """The scope of the ``k``-th bucket in ``FlatLayout.buckets`` order."""
    return BUCKET.format(k)
