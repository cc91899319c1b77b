"""The comparison that decides ``correct``: a training cell's first steps
through the timed path against the plain reference in ``reference/``.

Three numbers; each that ``limits/<workload>.json`` gives a limit is
compared with it:

- ``loss_gap``: over the compared steps, the largest |loss - loss_ref| /
  |loss_ref|;
- ``update_gap``: the first update as the optimizer gets it, by the worst
  leaf: |‖u‖ - ‖u_ref‖| / max(‖u_ref‖, median leaf ‖u_ref‖), where u is
  the optimizer's momentum after the first call (KD) or the first server
  update w_1 - w_0 (federated);
- ``change_gap``: the same for the parameters' change after the compared
  steps, w_n - w_0.

Leaves whose reference norm is under a thousandth of the median leaf's
move by round-off alone and are left out of the two leaf numbers.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

NAMES = ("loss_gap", "update_gap", "change_gap")
NEGLIGIBLE = 1e-3


@jax.jit
def leaf_norms(tree):
    """Per-leaf float32 L2 norms, in tree-flatten order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b))])


def leaf_gap(norms, ref) -> float:
    norms, ref = np.asarray(norms, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref))
    keep = ref >= NEGLIGIBLE * med
    if not keep.all():
        print(f"correct: {int((~keep).sum())} of {len(ref)} leaves move by "
              "round-off alone in the reference and are left out",
              file=sys.stderr)
    gap = np.abs(norms - ref) / np.maximum(ref, med)
    return float(np.max(gap[keep]))


def gaps(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` are readings: {"loss": [...], "update":
    per-leaf norms, "change": per-leaf norms}."""
    lp, lr = (np.asarray(prog["loss"], np.float64),
              np.asarray(ref["loss"], np.float64))
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    return {"loss_gap": loss_gap,
            "update_gap": leaf_gap(prog["update"], ref["update"]),
            "change_gap": leaf_gap(prog["change"], ref["change"])}


def limits_for(workload: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "limits", workload + ".json")
    with open(path) as f:
        return json.load(f)["limits"]


def judge(values: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}) over the numbers that have a
    limit; a NaN fails."""
    compared = {k: {"value": values[k], "limit": limits[k]} for k in NAMES
                if k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in compared.values())
    return bool(ok), compared
