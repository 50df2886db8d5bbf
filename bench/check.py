"""The comparison that decides ``correct``.

Four numbers, each against the limit its cell's ``limits/<cell>.json``
gives:

  loss_gap    relative gap between the program's loss and the
              reference's at the first step (the later steps' losses
              follow the program's bf16 gradient and swing by up to 100x
              more from seed to seed; they are kept in the result's
              ``losses``);
  grad_gap    the first gradient as the optimizer gets it, worked out from
              the state after one step, G = (X0 - X1) / eta - D1: the
              worst leaf's gap between the program's norm and the
              reference's;
  grad_median_gap  the same gradient's median leaf gap: steady from seed
              to seed where the worst leaf (the embedding, whose gradient
              the program accumulates in bf16) is not;
  change_gap  the state's change after ``check_steps`` steps (X - X0, and D,
              H, Hw, which start at 0): the worst leaf's gap between the
              norms.

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and the median leaf's (of the same state
part).  X leaves whose reference gradient is under a thousandth of the
median leaf's are left out of change_gap: their change is round-off."""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

PARTS = ("X", "D", "H", "Hw")
NUMBERS = ("loss_gap", "grad_gap", "grad_median_gap", "change_gap")
F32 = jnp.float32


def _sq(a):
    return jnp.sum(jnp.square(a.astype(F32)))


def _f32(a, like):
    """``a`` rounded to ``like``'s dtype, as float32."""
    return a.astype(like.dtype).astype(F32)


def grad_sq(x0, x1, d1, eta):
    """Per-leaf sum of squares of (x0 - x1) / eta - d1 (traced); x0 is
    rounded to the state's dtype first."""
    return [_sq((_f32(a, b) - b.astype(F32)) / eta - d.astype(F32))
            for a, b, d in zip(jax.tree_util.tree_leaves(x0),
                               jax.tree_util.tree_leaves(x1),
                               jax.tree_util.tree_leaves(d1))]


def change_sq(x0, X, D, H, Hw):
    """{part: per-leaf sum of squares of its change} (traced)."""
    out = {"X": [_sq(a.astype(F32) - _f32(b, a)) for a, b in zip(
        jax.tree_util.tree_leaves(X), jax.tree_util.tree_leaves(x0))]}
    for name, t in (("D", D), ("H", H), ("Hw", Hw)):
        out[name] = [_sq(a) for a in jax.tree_util.tree_leaves(t)]
    return out


def to_norms(sq) -> object:
    """Device sums of squares (list, or dict of lists) -> host norms."""
    if isinstance(sq, dict):
        return {k: to_norms(v) for k, v in sq.items()}
    return [math.sqrt(float(v)) for v in jax.device_get(list(sq))]


def add_sq(a, b):
    """Sum two per-node results of ``grad_sq`` / ``change_sq``."""
    if isinstance(a, dict):
        return {k: add_sq(a[k], b[k]) for k in a}
    return [x + y for x, y in zip(a, b)]


def leaf_gaps(prog: Sequence[float], ref: Sequence[float],
              keep: Sequence[bool] = ()) -> list:
    """Each leaf's gap by the rule in the module doc; None for a leaf
    left out."""
    keep = list(keep) or [True] * len(ref)
    kept = [r for r, k in zip(ref, keep) if k]
    med = float(np.median(kept)) if kept else 0.0
    out = []
    for p, r, k in zip(prog, ref, keep):
        den = max(r, med)
        if not k:
            out.append(None)
        elif not math.isfinite(p):
            out.append(math.inf)
        elif p == r:
            out.append(0.0)
        else:
            out.append(abs(p - r) / den if den > 0 else math.inf)
    return out


def _worst(gaps, names, prefix="") -> tuple:
    i = max((i for i, g in enumerate(gaps) if g is not None),
            key=lambda i: gaps[i], default=-1)
    return (gaps[i], prefix + names[i]) if i >= 0 else (0.0, "-")


def compare(prog: dict, ref: dict, leaf_names: Sequence[str]) -> Dict:
    """Numbers from two readings, each {"losses": [...], "grad": [...],
    "change": {part: [...]}}; returns {number: (value, where)}."""
    p, r = prog["losses"][0], ref["losses"][0]
    out = {"loss_gap": (abs(p - r) / abs(r) if math.isfinite(p)
                        else math.inf, "step 1")}
    grad = leaf_gaps(prog["grad"], ref["grad"])
    out["grad_gap"] = _worst(grad, leaf_names)
    out["grad_median_gap"] = (float(np.median(grad)), "median leaf")
    gmed = float(np.median(ref["grad"]))
    keep_x = [r >= 1e-3 * gmed for r in ref["grad"]]
    out["change_gap"] = max(
        (_worst(leaf_gaps(prog["change"][part], ref["change"][part],
                          keep_x if part == "X" else ()), leaf_names, part)
         for part in PARTS), key=lambda t: t[0])
    return out


def judge(numbers: Dict, limits: Dict[str, float]) -> tuple:
    """(correct, [(name, value, limit, where)]): every number at or under
    its limit.  A limit given as null marks a number that is read and not
    compared; a number the limits do not name fails."""
    rows, ok = [], True
    for name in NUMBERS:
        value, where = numbers[name]
        if name not in limits:
            ok = False
        elif limits[name] is not None:
            ok &= math.isfinite(value) and value <= limits[name]
        rows.append((name, value, limits.get(name), where))
    return ok, rows
