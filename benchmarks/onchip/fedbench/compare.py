"""The numbers that decide ``correct``: the program's first rounds against
the reference's, each number beside the limit the cell's limits file sets.

* ``loss0_gap`` — worst relative gap of a site's loss in the first round:
  SA-Net's forward pass and the task loss at the timed size, before any
  update.
* ``update_gap_median`` — the median leaf's gap between the norms of the
  global's change from the initial weights after the first rounds: the
  backward pass, clipping, AdamW, the Eq. 1 fold and the broadcast.

A gap of norms is taken against the reference's norm of that leaf or the
median leaf's, whichever is larger.  Leaves whose first gradient is
nought to rounding in the reference (under a thousandth of the median
leaf's) move under AdamW by round-off alone; they are left out by that
rule, not by name.  Later rounds' losses and the worst leaf are not
compared: AdamW's normalised step turns last-bit gradient differences
on near-zero elements into whole steps, so those numbers swing from seed
to seed as far for a sound run as for the lower-precision control.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

DEAD_LEAF = 1e-3


def leaf_gaps(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-leaf gap of norms (last axis: leaves), each against the
    reference's norm of that leaf or the median leaf's, the larger."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = np.maximum(np.abs(want), np.median(np.abs(want)))
    return np.abs(got - want) / np.maximum(floor, 1e-30)


def live_leaves(grad0_norms: np.ndarray) -> np.ndarray:
    """Leaves whose first gradient (largest over sites) is not nought."""
    g = np.max(np.asarray(grad0_norms, np.float64), axis=0)
    return g >= DEAD_LEAF * np.median(g)


def gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
         ) -> Dict[str, float]:
    """The numbers compared: the first round's losses always, the
    global's change where the program exposed it."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    rel = np.abs(lp[0] - lr[0]) / np.maximum(np.abs(lr[0]), 1e-30)
    out = {"loss0_gap": float(np.max(rel)) if np.all(np.isfinite(lp))
           else float("inf")}
    if prog.get("change_norms") is not None:
        g = leaf_gaps(prog["change_norms"], ref["change_norms"])[
            live_leaves(ref["grad0_norms"])]
        out["update_gap_median"] = float(np.median(g))
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Dict[str, Dict[str, float]]:
    """``{name: {"value", "limit"}}`` for every number that has a limit;
    every limited number has to be present."""
    out = {}
    for name, limit in limits.items():
        value = numbers.get(name, float("nan"))
        out[name] = {"value": value, "limit": limit}
    return out


def all_within(compared: Dict[str, Dict[str, float]]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in compared.values())
