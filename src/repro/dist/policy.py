"""Activation sharding policy: a dynamic context the model code queries.

Model forward passes are written once and call ``constrain(x, "residual")``
at layout-critical points; *which* layout that means is decided per
(mesh x shape) cell by ``repro.dist.sharding.activation_policy`` and bound
with the ``sharding_policy`` context manager in the step builders.  With no
policy bound (pure CPU unit tests, eval_shape tracing) ``constrain`` is the
identity, so model code never depends on a mesh being present.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, Iterator, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STACK = threading.local()


def _stack() -> list:
    if not hasattr(_STACK, "policies"):
        _STACK.policies = []
    return _STACK.policies


@contextmanager
def sharding_policy(mesh: Mesh,
                    act: Dict[str, P]) -> Iterator[None]:
    """Bind an activation policy ``{name: PartitionSpec}`` for ``mesh``.

    Nestable; the innermost binding wins.  The specs are *hints*: at
    ``constrain`` time any axis that does not evenly divide the matching
    tensor dimension is dropped rather than erroring, so one policy dict
    serves train / prefill / decode shapes alike.
    """
    _stack().append((mesh, dict(act)))
    try:
        yield
    finally:
        _stack().pop()


def current_policy() -> Optional[Tuple[Mesh, Dict[str, P]]]:
    s = _stack()
    return s[-1] if s else None


def _axis_size(mesh: Mesh, entry) -> int:
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for name in names:
        n *= mesh.shape[name]
    return n


def _fit_spec(mesh: Mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """Rank-adjust ``spec`` to ``shape`` and drop non-dividing axes."""
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    entries = entries[:len(shape)]
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None or dim % _axis_size(mesh, entry) != 0:
            out.append(None)
        else:
            out.append(entry)
    return P(*out)


def constrain(x: jax.Array, name: str) -> jax.Array:
    """Apply the active policy's constraint for ``name`` (identity if no
    policy is bound or the policy has no entry for ``name``).

    Inside a ``shard_map`` body the policy must name only the axes the
    body leaves automatic (``build_mlfabric_train_step`` binds such a
    policy); a spec naming a manual axis raises at trace time.
    """
    pol = current_policy()
    if pol is None:
        return x
    mesh, act = pol
    spec = act.get(name)
    if spec is None:
        return x
    fitted = _fit_spec(mesh, spec, x.shape)
    if all(e is None for e in fitted):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


# --------------------------------------------------------------------------- #
# phase-aware bounded-loss policy (DESIGN.md §12)
# --------------------------------------------------------------------------- #
class PhaseLossPolicy:
    """Training-phase-aware schedule for the bounded-loss transport tier.

    Early in training gradients are large and redundant, so the transport
    may accept loss and compress hard; as the loss curve flattens each
    surviving coordinate matters more, so the policy tightens the allowed
    transport loss, the top-k keep fraction, and the error-feedback
    residual bound — the same shape as §5.3's ``Div_max`` enforcement,
    applied to the data plane instead of replica divergence.

    ``phase()`` maps the recent *relative per-step improvement* of the
    observed loss into [0, 1]: 1 = steep descent (early), 0 = flat
    (converged).  With fewer than two observations the policy assumes
    early training (phase 1), i.e. it starts permissive.
    """

    def __init__(self, *, max_loss: float = 0.3, min_loss: float = 0.0,
                 max_keep: float = 1.0, min_keep: float = 0.05,
                 window: int = 8, ref_improvement: float = 0.05,
                 max_bound: float = 1.0, min_bound: float = 0.1):
        if not (0.0 <= min_loss <= max_loss < 1.0):
            raise ValueError(f"need 0 <= min_loss <= max_loss < 1: "
                             f"{min_loss}, {max_loss}")
        if not (0.0 < min_keep <= max_keep <= 1.0):
            raise ValueError(f"need 0 < min_keep <= max_keep <= 1: "
                             f"{min_keep}, {max_keep}")
        if window < 2 or ref_improvement <= 0.0:
            raise ValueError(f"bad window/ref_improvement: "
                             f"{window}, {ref_improvement}")
        self.max_loss, self.min_loss = max_loss, min_loss
        self.max_keep, self.min_keep = max_keep, min_keep
        self.window = int(window)
        self.ref_improvement = ref_improvement
        self.max_bound, self.min_bound = max_bound, min_bound
        self._history: list = []

    def observe(self, value: float) -> None:
        """Feed one loss-curve sample (call once per committed step)."""
        self._history.append(float(value))
        if len(self._history) > self.window:
            del self._history[:-self.window]

    def phase(self) -> float:
        h = self._history
        if len(h) < 2:
            return 1.0
        per_step = (h[0] - h[-1]) / (len(h) - 1)
        rel = per_step / max(abs(h[0]), 1e-12)
        return min(1.0, max(0.0, rel / self.ref_improvement))

    def allowed_loss(self) -> float:
        """Transport byte-loss fraction the trainer currently tolerates
        (what ``TransportConfig.phase_policy`` queries)."""
        p = self.phase()
        return self.min_loss + p * (self.max_loss - self.min_loss)

    def topk_keep(self) -> float:
        """Top-k keep fraction: aggressive early, near-dense when flat."""
        p = self.phase()
        return self.max_keep - p * (self.max_keep - self.min_keep)

    def residual_bound(self, ref_norm: float) -> float:
        """Error-feedback residual-norm ceiling, scaled to ``ref_norm``
        (typically the current gradient norm)."""
        p = self.phase()
        return ref_norm * (self.min_bound
                           + p * (self.max_bound - self.min_bound))


class PhaseLossCallback:
    """Trainer hook adapter: feeds batch-end loss into a PhaseLossPolicy.

    Duck-typed against ``core.harness.HookBus`` (like ``PhaseProfiler``):
    attach to any trainer's ``hooks=`` and the policy tracks the live loss
    curve without the transport tier knowing about the trainer.
    """

    def __init__(self, policy: PhaseLossPolicy, metric: str = "loss"):
        self.policy = policy
        self.metric = metric

    def on_batch_end(self, source, step: int, metrics=None) -> None:
        if metrics and self.metric in metrics:
            self.policy.observe(float(metrics[self.metric]))
