"""The numbers that decide ``correct`` for a training cell, from the
program's readings and the reference's (``reference/granite.py``).

- ``loss_gap``: the largest |program − reference| / reference over the
  first steps' losses of every replica.
- ``grad_norm_gap``: the first gradient as the optimizer holds it (the
  momentum buffer after one step), by the worst leaf: the gap between
  the program's and the reference's per-leaf norms, over the larger of
  the reference's norm of that leaf and of the median leaf.
- ``change_norm_gap``: the same for the parameters' change over the
  first steps. Leaves whose reference gradient is under a thousandth of
  the median leaf's are left out: they move by round-off alone.
"""
from __future__ import annotations

import numpy as np

#: a leaf whose reference gradient norm is below this share of the median
#: leaf's moves by round-off alone and is not compared
STILL_LEAF = 1e-3


def norm_gap(got: np.ndarray, want: np.ndarray,
             keep: np.ndarray | None = None) -> float:
    """Worst-leaf gap of per-leaf norms; ``got``/``want`` are (leaves,)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / np.maximum(scale, 1e-30)
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap)) if gap.size else 0.0


def moving_leaves(ref_grad_norms: np.ndarray) -> np.ndarray:
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= STILL_LEAF * np.median(g)


def program_readings(prog: dict) -> dict:
    """A driver's captured readings (``losses``: per step, (K,) each;
    ``grad``/``change``: (K, leaves)) in the per-replica form."""
    losses = np.stack([np.asarray(x) for x in prog["losses"]], axis=1)
    return {"replicas": [{"losses": losses[k], "grad": prog["grad"][k],
                          "change": prog["change"][k]}
                         for k in range(losses.shape[0])]}


def leaf_table(prog: dict, ref: dict, names: list[str]) -> list[str]:
    """Per replica and leaf, the program's and the reference's norms of
    the first gradient and of the change: for reading a failed check."""
    rows = []
    for k, (p, r) in enumerate(zip(prog["replicas"], ref["replicas"])):
        for i, n in enumerate(names):
            rows.append(f"replica {k} {n}: grad {p['grad'][i]:.6g} / "
                        f"{r['grad'][i]:.6g}, change {p['change'][i]:.6g} / "
                        f"{r['change'][i]:.6g}")
    return rows


def training_gaps(prog: dict, ref: dict) -> dict:
    """``prog``/``ref`` hold, per replica, ``losses`` (steps,),
    ``grad`` (leaves,) and ``change`` (leaves,). Returns the three gaps,
    each the worst over replicas."""
    out = {"loss_gap": 0.0, "grad_norm_gap": 0.0, "change_norm_gap": 0.0}
    for p, r in zip(prog["replicas"], ref["replicas"]):
        lg = np.abs(np.asarray(p["losses"], np.float64)
                    - np.asarray(r["losses"], np.float64))
        lg = float(np.max(lg / np.abs(np.asarray(r["losses"], np.float64))))
        keep = moving_leaves(r["grad"])
        out["loss_gap"] = max(out["loss_gap"], lg)
        out["grad_norm_gap"] = max(out["grad_norm_gap"],
                                   norm_gap(p["grad"], r["grad"]))
        out["change_norm_gap"] = max(out["change_norm_gap"],
                                     norm_gap(p["change"], r["change"],
                                              keep))
    return out
