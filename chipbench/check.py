"""The comparison that decides ``correct``: the program's readings of its
first steps against the reference's.

The numbers, each compared where the workload file gives it a limit:

* ``loss_gap``: the largest relative gap of a step's loss, over the first
  ``check_steps`` steps (steps 0 and on run the update of the step before).
* ``grad_gap``: the first gradient as the optimizer got it (the first
  moment after one step over (1 - b1)), per slice of each leaf: the gap
  between the program's norm and the reference's, over the reference's
  norm of that leaf or of the median leaf, whichever is larger; the worst.
* ``second_grad_gap``: the same for the second step's gradient as the
  optimizer got it (read from the second moments after steps 0 and 1):
  in a cell that refreshes at step 1, it is taken in the subspace step 1
  drew.
* ``change_gap``: the same for the norm of each leaf's change after
  ``check_steps`` steps.  Leaves whose reference gradient is under a
  thousandth of the median leaf's (a key's bias under softmax has none in
  exact arithmetic) move under Adam by round-off alone and are left out.
  ``median_change_gap``: the median over those leaves of the same gap.
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Tuple

NOUGHT = 1e-3  # gradient under this share of the median leaf's: none


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               names: List[str]) -> Dict[str, float]:
    med = statistics.median(ref[n] for n in names)
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
            for n in names}


def _worst(per_leaf: Dict[str, float]) -> Tuple[float, str]:
    at = max(per_leaf, key=per_leaf.get)
    return per_leaf[at], at


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr):
        raise ValueError(f"{len(lp)} program losses, {len(lr)} reference")
    loss = [abs(a - b) / abs(b) for a, b in zip(lp, lr)]
    names = sorted(ref["grad_norms"])
    if sorted(prog["grad_norms"]) != names:
        raise ValueError("program and reference read different leaves")
    grad, grad_at = _worst(_leaf_gaps(prog["grad_norms"], ref["grad_norms"],
                                      names))
    second, second_at = _worst(_leaf_gaps(
        prog["second_grad_norms"], ref["second_grad_norms"], names))
    med = statistics.median(ref["grad_norms"].values())
    moved = [n for n in names if ref["grad_norms"][n] >= NOUGHT * med]
    per_leaf = _leaf_gaps(prog["change_norms"], ref["change_norms"], moved)
    change, change_at = _worst(per_leaf)
    return {
        "loss_gap": max(loss),
        "grad_gap": grad,
        "second_grad_gap": second,
        "change_gap": change,
        "median_change_gap": statistics.median(per_leaf.values()),
        "where": {
            "loss_gap": f"step {loss.index(max(loss))}",
            "grad_gap": grad_at,
            "second_grad_gap": second_at,
            "change_gap": change_at,
            "left_out_of_change": sorted(set(names) - set(moved)),
        },
    }


def verdict(found: Dict[str, Any], limits: Dict[str, Optional[float]]
            ) -> Tuple[bool, Dict[str, Dict[str, Any]]]:
    """(correct, {number: {"value", "limit"}}).  A number with no limit set
    yet cannot pass."""
    table, ok = {}, bool(limits)
    for name, limit in limits.items():
        value = found[name]
        table[name] = {"value": value, "limit": limit}
        ok = ok and limit is not None and value <= limit
    return ok, table
