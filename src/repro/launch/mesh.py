"""Production mesh builders.

The paper's placement (§4.2) maps onto the axes as:
  pod   -> DC            (pipeline stages cross it; thin DCN = WAN)
  data  -> DP inside a DC (all-reduce rings never leave a pod)
  model -> TP/EP on fast interconnect

Every axis is ``Auto``: GSPMD propagates shardings through the model
(``jax.make_mesh`` defaults to ``Explicit`` axes, which reject the
model's un-annotated gathers), and the axes a partial-manual
``shard_map`` leaves automatic stay constrainable inside it.

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(*, multi_pod: bool = False):
    """Mesh over whatever devices exist (one chip, a four-chip host, or
    CPU virtual devices).  ``multi_pod`` splits them into two pods: four
    devices give ``(pod=2, data=1, model=2)``, eight ``(2, 2, 2)``."""
    n = len(jax.devices())
    if multi_pod:
        if n < 2 or n % 2:
            raise ValueError(f"multi_pod mesh needs an even device count, got {n}")
        per = n // 2
        dp = 2 if per >= 4 and per % 2 == 0 else 1
        return auto_mesh((2, dp, per // dp), ("pod", "data", "model"))
    dp = 2 if n % 2 == 0 else 1
    return auto_mesh((dp, n // dp), ("data", "model"))
