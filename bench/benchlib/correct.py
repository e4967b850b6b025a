"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against a limit of its own (``bench/limits/<cell>.json``):

* ``loss_gap``: over the first three steps, the largest
  ``|loss_program - loss_reference| / |loss_reference|``;
* ``grad_gap``: the first step's gradient as the optimizer got it,
  worked out from the momentum state after one step (``-history / lr``);
* ``delta_gap``: the parameters' change over the three steps, read before
  step 4 takes them.

For the last two, each leaf is one weight of one layer.  The number is the
worst leaf's gap between the program's norm and the reference's, over the
larger of the reference's norm of that leaf and of the median leaf.  Leaves
whose reference gradient is under a thousandth of the median leaf's (a key
bias under softmax, padding) move by round-off alone and are left out of
both, by that rule and not by name.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

SMALL_LEAF = 1e-3        # share of the median leaf's gradient norm
NUMBERS = ("loss_gap", "grad_gap", "delta_gap")


UNSTACKED = ("embed", "lm_head", "final_norm")


def _norms(tree: Dict) -> Dict:
    out = {}
    for name, x in tree.items():
        sq = jnp.square(x.astype(jnp.float32))
        if name in UNSTACKED:
            out[name] = jnp.sqrt(jnp.sum(sq))[None]
        else:                                  # [layers, ...]
            out[name] = jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, x.ndim))))
    return out


leaf_norms = jax.jit(_norms)
"""Per-leaf norms of a reference-named tree: one per layer of a stacked
weight (``[layers, ...]``), one for the embedding and the final norm."""


@jax.jit
def _diff_norms(a: Dict, b: Dict) -> Dict:
    return _norms(jax.tree.map(lambda x, y: x.astype(jnp.float32)
                               - y.astype(jnp.float32), a, b))


def diff_norms(a: Dict, b: Dict) -> Dict[str, np.ndarray]:
    return host(_diff_norms(a, b))


def host(norms: Dict) -> Dict[str, np.ndarray]:
    return {k: np.asarray(jax.device_get(v), np.float64)
            for k, v in norms.items()}


def _flat(norms: Dict[str, np.ndarray]) -> Dict[Tuple[str, int], float]:
    return {(k, i): float(v) for k, arr in norms.items()
            for i, v in enumerate(np.ravel(arr))}


def leaf_gap(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
             keep: List[Tuple[str, int]]) -> Tuple[float, str]:
    p, r = _flat(prog), _flat(ref)
    med = float(np.median([r[k] for k in keep]))
    worst, where = 0.0, ""
    for k in keep:
        gap = abs(p[k] - r[k]) / max(r[k], med)
        if not np.isfinite(gap):
            return float("inf"), f"{k[0]}[{k[1]}]"
        if gap > worst:
            worst, where = gap, f"{k[0]}[{k[1]}]"
    return worst, where


def kept_leaves(ref_grad: Dict[str, np.ndarray]) -> List[Tuple[str, int]]:
    r = _flat(ref_grad)
    med = float(np.median(list(r.values())))
    return sorted(k for k, v in r.items() if v >= SMALL_LEAF * med)


def readings(prog: Dict, ref: Dict) -> Dict:
    """``prog`` and ``ref`` each hold ``losses`` (three floats),
    ``grad`` and ``delta`` (per-leaf norms).  Returns the three numbers,
    the worst leaf of each and the leaves left out."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    keep = kept_leaves(ref["grad"])
    grad_gap, grad_at = leaf_gap(prog["grad"], ref["grad"], keep)
    delta_gap, delta_at = leaf_gap(prog["delta"], ref["delta"], keep)
    left_out = sorted(set(_flat(ref["grad"])) - set(keep))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "delta_gap": delta_gap, "grad_worst": grad_at,
            "delta_worst": delta_at,
            "left_out": [f"{k}[{i}]" for k, i in left_out]}


def judge(read: Dict, limits: Dict) -> Tuple[bool, Dict]:
    """``correct`` and each number beside its limit, in a fixed order."""
    compared = {}
    ok = True
    for name in NUMBERS:
        value, limit = read[name], float(limits[name])
        compared[name] = {"value": value, "limit": limit}
        if not value <= limit:          # NaN fails too
            ok = False
    return ok, compared
