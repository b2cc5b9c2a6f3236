"""Sharding rules and divisibility-aware constraint helpers.

The production layout (DESIGN.md §5) follows the paper's placement:
  - ``pod``   axis: pipeline stages (paper: PP across DCs)
  - ``data``  axis: data parallelism (paper: DP rings intra-DC)
  - ``model`` axis: tensor/expert parallelism (paper: TP/EP on NVLink)

``constrain`` is safe to call from model code unconditionally: it no-ops
outside a mesh context and drops mesh axes that do not divide the
corresponding dimension (e.g. granite's kv=1 heads on a 16-way model axis,
or qwen2-moe's 60 experts).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


import contextlib
import threading

_TLS = threading.local()


@contextlib.contextmanager
def constraints_disabled():
    """Temporarily no-op ``constrain`` — used inside the manual-pod
    shard_map pipeline where XLA's SPMD partitioner cannot handle some
    constrained gather/scatter patterns (MoE dispatch)."""
    prev = getattr(_TLS, "off", False)
    _TLS.off = True
    try:
        yield
    finally:
        _TLS.off = prev


def _ambient_mesh():
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _fit_spec(shape: Tuple[int, ...], spec: P, mesh) -> Optional[P]:
    """Drop axes that don't divide the dim; None if nothing remains.
    Trailing unsplit dims are left out, as a compiled step writes the
    shardings of what it returns, so a state placed by these specs and
    the state a step hands back compare equal."""
    axes = dict(mesh.shape)
    fitted = []
    changed = False
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            fitted.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        ok = []
        size = 1
        for n in names:
            if n in axes:
                size *= axes[n]
                ok.append(n)
        if ok and dim % size == 0:
            fitted.append(tuple(ok) if len(ok) > 1 else ok[0])
        else:
            fitted.append(None)
            changed = True
    if all(f is None for f in fitted):
        return None
    while fitted[-1] is None:
        fitted.pop()
    return P(*fitted)


def _auto_only(spec: P, mesh) -> P:
    """``spec`` without the axes ``mesh`` marks ``Manual``: inside a
    ``shard_map`` those are already split by hand, and a constraint may
    name only the axes left to GSPMD."""
    manual = set(mesh.manual_axes)
    if not manual:
        return spec

    def keep(entry):
        names = entry if isinstance(entry, tuple) else (entry,)
        left = tuple(n for n in names if n is not None and n not in manual)
        return (left if len(left) > 1 else left[0]) if left else None

    return P(*(keep(e) for e in spec))


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that degrades gracefully.

    No-op when there is no ambient mesh (plain CPU tests) or when no axis
    of ``spec`` fits the array's shape; inside a ``shard_map`` it leaves
    out the manual axes (the pipeline's ``pod`` and ``data``).
    """
    if getattr(_TLS, "off", False):
        return x
    mesh = _ambient_mesh()
    if mesh is None or not hasattr(x, "shape"):
        return x
    fitted = _fit_spec(x.shape, _auto_only(spec, mesh), mesh)
    if fitted is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


# ---------------------------------------------------------------------------
# canonical specs for the training/serving state
# ---------------------------------------------------------------------------

# logical rules: tensor-name suffix -> PartitionSpec (applied by best effort)
PARAM_RULES: Dict[str, P] = {
    # attention projections: shard the head (output-feature) dim
    "wq": P(None, "model"),
    "wk": P(None, "model"),
    "wv": P(None, "model"),
    "wo": P("model", None),
    # MLA
    "w_dkv": P(None, None),
    "w_uk": P(None, "model"),
    "w_uv": P(None, "model"),
    # FFN
    "w_gate": P(None, "model"),
    "w_up": P(None, "model"),
    "w_down": P("model", None),
    # embedding table: shard the feature dim (gather over an unsharded
    # vocab dim partitions trivially, incl. inside the pipeline's manual
    # region); LM head: shard the vocab dim (big-vocab CE memory)
    "embed": P(None, "model"),
    "lm_head": P(None, "model"),
    "router": P(None, None),
    # mamba2: head-sharded TP (see repro.models.ssm)
    "w_z": P(None, "model"),
    "w_x": P(None, "model"),
    "w_bc": P(None, None),
    "w_dt": P(None, None),
    "conv_x": P(None, "model"),
    "conv_bc": P(None, None),
    "w_out": P("model", None),
    "norm_scale": P("model"),
    # rwkv6: head-sharded time-mix, model-sharded channel-mix
    "wr": P(None, "model"),
    "wg": P(None, "model"),
    "w0": P("model"),
    "w_lora_a": P(None, None),
    "w_lora_b": P(None, "model"),
    "u": P("model", None),
    "ck": P(None, "model"),
    "cv": P("model", None),
    "cr": P(None, "model"),
    # norms / scalars replicated
}

MOE_RULES: Dict[str, Tuple[P, ...]] = {
    # routed experts: shard the expert dim (EP); when the expert count
    # does not divide the model axis (qwen2-moe: 60 experts on 16), fall
    # back to sharding the FFN feature dim so the weights never replicate
    "w_gate": (P("model", None, None), P(None, None, "model")),
    "w_up": (P("model", None, None), P(None, None, "model")),
    "w_down": (P("model", None, None), P(None, "model", None)),
}


def param_spec_candidates(
    path: Tuple[str, ...], shape: Tuple[int, ...], stacked: bool
) -> Tuple[P, ...]:
    """Candidate specs for a parameter leaf, best first.  ``stacked`` =>
    leading layer axis.  The caller picks the first that fits the mesh."""
    name = path[-1]
    in_moe = (
        any(p in ("moe", "experts") for p in path[:-1])
        and name in MOE_RULES
        and len(shape) >= 3
    )
    cands = MOE_RULES[name] if in_moe else (PARAM_RULES.get(name, P()),)
    if stacked:
        cands = tuple(P(None, *tuple(c)) for c in cands)
    return cands


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...], stacked: bool) -> P:
    return param_spec_candidates(path, shape, stacked)[0]


def _tree_paths(tree: Any):
    return jax.tree_util.tree_flatten_with_path(tree)


def _add_fsdp_axis(spec: P, shape: Tuple[int, ...], mesh: Mesh, min_bytes=2**22) -> P:
    """ZeRO/FSDP-style 2D sharding: also shard a large, still-unsharded dim
    of big matrices over the ``data`` axis (weights are all-gathered on
    use; params + Adam state memory drops by the data-axis size)."""
    if "data" not in mesh.shape:
        return spec
    n = 1
    for d in shape:
        n *= d
    if n * 4 < min_bytes or len(shape) < 2:
        return spec
    entries = list(tuple(spec) + (None,) * (len(shape) - len(spec)))
    dp = mesh.shape["data"]
    # pick the largest unsharded dim divisible by the data axis
    cands = [
        (shape[i], i) for i, e in enumerate(entries) if e is None and shape[i] % dp == 0 and shape[i] > 1
    ]
    if not cands:
        return spec
    _, i = max(cands)
    entries[i] = "data"
    return P(*entries)


def make_param_shardings(
    params_shape: Any,
    mesh: Mesh,
    stacked_prefixes=("layers", "groups"),
    *,
    fsdp: bool = False,
    stages: bool = True,
):
    """Build a NamedSharding pytree for a params(-shape) pytree.

    ``stages``: where the mesh has more than one ``pod``, split the
    stacked layer axis over it, so that each pipeline stage holds only its
    own layers and their optimizer state (``parallel/pipeline.py``); pass
    False where the pods are replicas of the whole model."""
    split_layers = stages and dict(mesh.shape).get("pod", 1) > 1

    def one(path, leaf):
        names = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path if hasattr(p, "key")
        )
        stacked = any(n in stacked_prefixes for n in names)
        for spec in param_spec_candidates(names or ("",), leaf.shape, stacked):
            if stacked and split_layers:
                spec = P("pod", *tuple(spec)[1:])
            fitted = _fit_spec(leaf.shape, spec, mesh)
            if fitted is not None:
                if fsdp:
                    fitted2 = _fit_spec(
                        leaf.shape, _add_fsdp_axis(fitted, leaf.shape, mesh), mesh
                    )
                    if fitted2 is not None:
                        return NamedSharding(mesh, fitted2)
                return NamedSharding(mesh, fitted)
        return NamedSharding(mesh, P())

    leaves, treedef = _tree_paths(params_shape)
    shardings = [one(path, leaf) for path, leaf in leaves]
    return jax.tree_util.tree_unflatten(treedef, shardings)


def batch_spec(ndim: int) -> P:
    """Shard the batch (dim 0) over data; rest replicated."""
    return P("data", *([None] * (ndim - 1)))


def make_batch_shardings(batch_shape: Any, mesh: Mesh):
    def one(leaf):
        spec = batch_spec(len(leaf.shape))
        # VLM positions are (3, B, T): batch is dim 1
        if len(leaf.shape) == 3 and leaf.shape[0] == 3 and leaf.dtype == jnp.int32:
            spec = P(None, "data", None)
        fitted = _fit_spec(leaf.shape, spec, mesh)
        return NamedSharding(mesh, fitted if fitted is not None else P())

    return jax.tree_util.tree_map(one, batch_shape)


def make_cache_shardings(cache_shape: Any, mesh: Mesh):
    """KV caches: batch on data, head/feature dims on model where they fit."""

    def one(leaf):
        if len(leaf.shape) == 5:  # (L, B, S, Hkv, Dh)
            spec = P(None, "data", None, "model", None)
        elif len(leaf.shape) == 4:  # (L, B, S, d) latent / conv state
            spec = P(None, "data", None, None)
        elif len(leaf.shape) == 3:  # (L, B, S) positions
            spec = P(None, "data", None)
        else:
            spec = P()
        fitted = _fit_spec(leaf.shape, spec, mesh)
        return NamedSharding(mesh, fitted if fitted is not None else P())

    return jax.tree_util.tree_map(one, cache_shape)
