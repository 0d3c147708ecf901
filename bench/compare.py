"""The comparison that decides `correct` for a training cell.

The numbers, each against its limit (bench/workloads/<cell>.json):

  loss_gap    the largest |loss_k - loss_ref_k| over the steps followed,
              in nats
  grad_gap    the gap between the norms of the first gradient as the
              optimizer got it (||p_1 - p_0|| / lr) over the whole model,
              over the reference's norm
  update_gap  the same for the change of the parameters after the steps
              followed (||p_n - p_0||)

The norms are taken over all leaves together, not by the worst leaf: in
a bf16 model trained by SGD most of a step's updates lie under half a
unit in the last place of the weights they update, so a leaf whose
elements sit near that threshold moves by a different count of elements
for a gradient that differs by rounding alone. Such leaves read gaps of
0.3 to 1 in sound runs and in the reference computed in bf16 alike
(PERF.md, section 2). Leaves whose uncompressed first gradient in the
reference is under a thousandth of the median leaf's are left out: they
move by round-off alone.

A number is compared where the cell gives it a limit; PERF.md says why a
number goes without one.
"""
from __future__ import annotations

import math
from statistics import median
from typing import Dict

LEAF_FLOOR = 1e-3


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], keep) -> float:
    """|norm of the kept leaves together - the reference's| over the
    reference's."""
    a = math.sqrt(sum(prog[p] ** 2 for p in keep))
    b = math.sqrt(sum(ref[p] ** 2 for p in keep))
    if not math.isfinite(a):
        return math.inf
    return abs(a - b) / b if b > 0 else math.inf


def kept_leaves(ref: dict):
    g = ref["true_grad_norms"]
    med = median(g.values())
    return sorted(p for p, v in g.items() if v >= LEAF_FLOOR * med)


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("program and reference leaves differ")
    keep = kept_leaves(ref)
    loss = max((abs(a - b) if math.isfinite(a) else math.inf)
               for a, b in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": loss,
            "grad_gap": norm_gap(prog["grad_norms"], ref["grad_norms"],
                                 keep),
            "update_gap": norm_gap(prog["update_norms"],
                                   ref["update_norms"], keep)}


def checks(nums: Dict[str, float], limits: Dict[str, float]) -> dict:
    """The numbers the cell gives a limit, each beside it."""
    return {k: {"value": nums[k], "limit": limits[k]} for k in limits}


def passed(chk: dict) -> bool:
    return bool(chk) and all(c["value"] <= c["limit"] for c in chk.values())
