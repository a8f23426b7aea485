"""The numbers that decide `correct`, and their limits.

Each number is a gap between what the timed path produced and what the
plain reference computes from the same seed:

  loss_gap     worst relative gap of a step's loss over the compared steps;
  gnorm_gap    worst relative gap of a step's pre-clip global grad norm;
  grad0_gap    the first gradient as the optimizer got it (clipped), worked
               out from its first moment after one step: the worst leaf's
               gap of norms, over the larger of that leaf's reference norm
               and the median leaf's;
  update_gap   the same for each leaf's change over the compared steps.
               Leaves whose reference gradient is under a thousandth of
               the median leaf's move by round-off alone and are left out.

Limits live in `limits/<workload>.json`, one file per cell, with the
readings each was set from.
"""
from __future__ import annotations

import json
import statistics
from pathlib import Path
from typing import Dict, Optional

ROUNDOFF_SHARE = 1e-3


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


def leaf_gap(got: Dict[str, float], want: Dict[str, float],
             skip=frozenset()) -> float:
    """Worst leaf: |‖got‖ - ‖want‖| / max(‖want‖, median leaf's ‖want‖)."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    med = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], med)
               for k in want if k not in skip)


def roundoff_leaves(grad0: Dict[str, float]) -> frozenset:
    med = statistics.median(grad0.values())
    return frozenset(k for k, v in grad0.items() if v < ROUNDOFF_SHARE * med)


def train_gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """`prog` and `ref` as `reference.readings` returns them."""
    n = len(ref["loss"])
    skip = roundoff_leaves(ref["grad0"])
    return {
        "loss_gap": max(rel_gap(prog["loss"][t], ref["loss"][t])
                        for t in range(n)),
        "gnorm_gap": max(rel_gap(prog["grad_norm"][t], ref["grad_norm"][t])
                         for t in range(n)),
        "grad0_gap": leaf_gap(prog["grad0"], ref["grad0"]),
        "update_gap": leaf_gap(prog["change"], ref["change"], skip),
    }


def load_limits(root: Path, workload: str) -> Optional[Dict[str, float]]:
    path = root / "limits" / f"{workload}.json"
    if not path.exists():
        return None
    return {k: float(v["limit"]) for k, v in
            json.loads(path.read_text())["limits"].items()}


def judge(gaps: Dict[str, float], limits: Optional[Dict[str, float]]):
    """(correct, {name: {"value", "limit"}}).  A cell without limits, or a
    number that is not finite, is not correct."""
    checks = {k: {"value": v, "limit": None if limits is None
                  else limits.get(k)} for k, v in gaps.items()}
    ok = limits is not None and all(
        c["limit"] is not None and c["value"] == c["value"]
        and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
