"""The numbers that decide ``correct``, each held against its limit from
``limits/<workload>.json``.

Training compares three numbers with the plain reference:

* ``grad_gap``: the worst leaf's gap between the norm of the first
  gradient as the optimizer gets it (clipped) and the reference's;
* ``grad_block_gap``: the same gap for the norms of that gradient's
  row-major blocks, 64 a leaf (``weights.block_norms``).  One norm a
  leaf is one projection of the gradient's error, which on some seeds
  lies near zero on every leaf even for a float8 gradient; the worst of
  many block norms does not (PERF.md);
* ``change_gap``: the worst leaf's gap between the norm of the
  parameters' change over the first steps and the reference's.

A leaf's gap is ``|program norm - reference norm|`` over the larger of
the reference's norm of that leaf and of the median leaf (for blocks:
of that block and of the median block).  Leaves whose
reference gradient is under a thousandth of the median leaf's (nought to
rounding, so Adam moves them by round-off) are left out of the change.

``loss_gap``, the largest ``|loss - ref| / |ref|`` over the first steps,
is logged and not compared: the float8 control reads no higher on it
than the program does (PERF.md).
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional, Sequence

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ZERO_GRAD_SHARE = 1e-3


def load_limits(workload: str, root: str = HERE) -> Dict[str, float]:
    with open(os.path.join(root, "limits", f"{workload}.json")) as f:
        return json.load(f)["limits"]


def worst_leaf_gap(prog: Sequence[float], ref: Sequence[float],
                   keep: Optional[np.ndarray] = None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    med = float(np.median(ref[keep]))
    denom = np.maximum(ref[keep], med)
    return float(np.max(np.abs(prog[keep] - ref[keep]) / denom))


def moving_leaves(ref_grad_norms: Sequence[float]) -> np.ndarray:
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= ZERO_GRAD_SHARE * np.median(g)


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog``/``ref`` hold ``losses``, ``grad_norms`` and ``change_norms``."""
    keep = moving_leaves(ref["grad_norms"])
    return {
        "grad_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "grad_block_gap": worst_leaf_gap(prog["grad_blocks"], ref["grad_blocks"]),
        "change_gap": worst_leaf_gap(prog["change_norms"], ref["change_norms"], keep),
    }


def checks(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each compared number beside its limit; a number without a limit is
    an error in the limits file."""
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return {k: {"value": float(v), "limit": float(limits[k])} for k, v in numbers.items()}


def all_within(chk: Dict[str, dict]) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in chk.values())
