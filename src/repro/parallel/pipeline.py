"""Cross-pod pipeline parallelism — the paper's "PP across DCs" mapped to
TPU (DESIGN.md §2).

The ``pod`` mesh axis carries pipeline stages; ``data`` carries DP within a
pod; ``model`` carries TP.  The step is a circular-rotation microbatch
pipeline inside a *partial-auto* shard_map: manual over {pod, data}
(``lax.ppermute`` moves stage-boundary activations across the inter-pod
DCN; per-data-shard token work is local, which also sidesteps XLA SPMD
partitioner failures around MoE gather/scatter in manual subgroups),
while GSPMD keeps handling the ``model`` axis (TP) automatically.
Autodiff through the scan+ppermute yields the reversed-permutation
backward pipeline for free; the psum over ``data`` in the loss transposes
into the DP gradient all-reduce.

Boundary modes — the TPU-native reading of the paper's two transports:
  * ``direct``  (Varuna / PyTorch-one-TCP analogue): the activation is
    model-axis *replicated* when it crosses the pod boundary, so all 16
    chips of a model group send identical bytes over the thin DCN — 16×
    redundant traffic.
  * ``striped`` (Atlas multi-TCP + temporal-sharing analogue): constrain
    the activation to be model-sharded before the ppermute (a local slice,
    no comm), so each chip carries 1/16 of the unique bytes over DCN, and
    all-gather it back over the fast intra-pod ICI on the receiving side.
  The dry-run roofline's collective-bytes term makes the 16× visible.

Non-divisible layer counts (deepseek-v2-lite: 27, zamba2: 9 groups) are
padded with exact-identity zero layers (residual blocks with zero weights
add exactly 0; zamba2's shared block is disabled by its zero-padded
per-group gate), keeping stages structurally uniform.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.modules import ModelConfig
from repro.models.transformer import PipelineParts, build_pipeline_parts
from repro.parallel.sharding import constraints_disabled, make_param_shardings
from repro.scopes import EMBED, PIPELINE, STAGE_BOUNDARY


def pad_layer_stack(layers: Any, num_stages: int) -> Any:
    """Zero-pad the leading (layer) axis to a multiple of num_stages.

    Zero weights make a residual block an exact identity (attn/FFN/Mamba
    deltas are 0), so padding does not change the function.
    """

    def pad(leaf):
        L = leaf.shape[0]
        pad_n = (-L) % num_stages
        if pad_n == 0:
            return leaf
        return jnp.concatenate(
            [leaf, jnp.zeros((pad_n,) + leaf.shape[1:], leaf.dtype)], 0
        )

    return jax.tree.map(pad, layers)


def padded_num_layers(num_layers: int, num_stages: int) -> int:
    return num_layers + ((-num_layers) % num_stages)


def make_pipeline_loss(
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    n_micro: int = 4,
    boundary: str = "striped",
    fsdp: bool = False,
) -> Callable[[Dict, Dict], jax.Array]:
    """Build loss(params, batch) running PP over the mesh's ``pod`` axis.

    ``fsdp`` names the layout the caller places the parameters in
    (``make_param_shardings``); their gradients come back in it, so a
    train step hands back parameters and moments as it took them."""
    assert boundary in ("striped", "direct")
    assert not cfg.tie_embeddings, (
        "pipeline requires untied embeddings: a tied table is consumed in "
        "both the GSPMD and manual regions, which XLA's partitioner rejects"
    )
    parts = build_pipeline_parts(cfg)
    S = mesh.shape["pod"]
    DP = mesh.shape["data"]

    def loss_fn(params: Dict, batch: Dict) -> jax.Array:
        # a no-op forward whose transpose lays the gradients out as the
        # parameters: left to GSPMD, the norm scales' come back split
        # over ``model`` and every later step compiles a second program
        params = jax.lax.with_sharding_constraint(
            params, make_param_shardings(params, mesh, fsdp=fsdp))
        # ---- static input prep (ints only; differentiable inputs and the
        # embedding lookup live INSIDE the manual region — a `take` whose
        # cotangent crosses the GSPMD/manual boundary trips an XLA SPMD
        # partitioner CHECK) ----
        if "embeds" in batch:
            B, T = batch["embeds"].shape[:2]
        else:
            B, T = batch["tokens"].shape
        assert B % (n_micro * DP) == 0, (B, n_micro, DP)
        mb = B // n_micro

        if "positions" in batch:
            positions = batch["positions"]
        elif cfg.mrope_sections is not None:
            pos2 = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
            positions = jnp.broadcast_to(pos2[None], (3, B, T))
        else:
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        if positions.ndim == 3:  # (3, B, T) M-RoPE
            pos_mb = positions.reshape(3, n_micro, mb, T).transpose(1, 0, 2, 3)
            pos_spec = P(None, None, "data", None)
        else:
            pos_mb = positions.reshape(n_micro, mb, T)
            pos_spec = P(None, "data", None)

        targets = batch.get("labels")
        if targets is None:
            targets = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
            mask = jnp.ones_like(targets, jnp.float32).at[:, -1].set(0.0)
        else:
            mask = batch.get("mask")
            if mask is None:
                mask = jnp.ones_like(targets, jnp.float32)
        t_mb = targets.reshape(n_micro, mb, T)
        m_mb = mask.reshape(n_micro, mb, T)

        if "embeds" in batch:
            inp_mb = batch["embeds"].astype(cfg.dtype).reshape(n_micro, mb, T, -1)
            inp_spec = P(None, "data", None, None)
            token_input = False
        else:
            inp_mb = batch["tokens"].reshape(n_micro, mb, T)
            inp_spec = P(None, "data", None)
            token_input = True

        layers = pad_layer_stack(params[parts.layer_key], S)
        rest = {k: v for k, v in params.items() if k != parts.layer_key}

        inner = functools.partial(
            _pipeline_inner,
            parts=parts,
            cfg=cfg,
            S=S,
            DP=DP,
            n_micro=n_micro,
            boundary=boundary,
            token_input=token_input,
        )
        sm = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P("pod"), layers),
                jax.tree.map(lambda _: P(), rest),
                inp_spec,
                pos_spec,
                P(None, "data", None),
                P(None, "data", None),
            ),
            out_specs=(P(), P()),
            # partial-auto: GSPMD keeps handling TP on ``model``
            axis_names={"pod", "data"},
            check_vma=False,
        )
        with jax.named_scope(PIPELINE):
            loss, aux = sm(layers, rest, inp_mb, pos_mb, t_mb, m_mb)
        return loss + aux

    return loss_fn


def _constrain_auto(x, spec):
    """``with_sharding_constraint`` over the axes the manual region leaves
    automatic (``model``), on the ambient abstract mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


def _pipeline_inner(
    layers, rest, inp_mb, pos_mb, t_mb, m_mb, *, parts, cfg, S, DP,
    n_micro, boundary, token_input,
):
    """Manual over {pod, data}: ``layers`` is this stage's (L/S, ...) slice;
    token arrays are this data-shard's slice."""
    my = jax.lax.axis_index("pod")  # this pod's stage index
    steps = n_micro + S - 1
    if token_input:
        # embedding lookup with device-local indices: the VJP scatter-add
        # stays inside the manual region (no partitioned scatter).
        with jax.named_scope(EMBED):
            x_mb = jnp.take(rest["embed"], inp_mb, axis=0).astype(cfg.dtype)
    else:
        x_mb = inp_mb
    mb, T, Dm = x_mb.shape[1:]

    def stage_fn(x, positions):
        def body(h, lp):
            # the layers' own sharding constraints stay off here (XLA's
            # partitioner rejects some in the manual subgroup: MoE
            # dispatch) — GSPMD still propagates the model-axis (TP)
            # shardings from the parameters.
            with constraints_disabled():
                h, aux = parts.layer(lp, rest, h, positions)
            return h, aux

        if cfg.remat != "none":
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, layers)
        return x, jnp.sum(auxs)

    idx = lambda arr, i: jax.lax.dynamic_index_in_dim(arr, i, 0, keepdims=False)

    def rot(carry, t):
        buf = carry  # activation arriving from the previous stage
        m_in = jnp.clip(t, 0, n_micro - 1)  # stage-0 microbatch index
        x0 = idx(x_mb, m_in)
        inp = jnp.where(my == 0, x0, buf)
        m_mine = t - my  # microbatch this stage works on (may be invalid)
        pos = idx(pos_mb, jnp.clip(m_mine, 0, n_micro - 1))
        y, aux = stage_fn(inp, pos)
        valid_mine = jnp.logical_and(m_mine >= 0, m_mine < n_micro)
        aux = aux * valid_mine.astype(jnp.float32)

        # ---- stage boundary: direct (replicated) vs striped (sharded) ----
        # NB: must force the sharding even when it is full replication
        # (repro.parallel.sharding.constrain treats all-None as a no-op),
        # otherwise GSPMD propagation picks its own layout and the two
        # modes become indistinguishable.
        with jax.named_scope(STAGE_BOUNDARY):
            if boundary == "striped":
                y_send = _constrain_auto(y, P(None, None, "model"))
                buf_next = jax.lax.ppermute(
                    y_send, "pod", [(i, i + 1) for i in range(S - 1)]
                )
            else:
                # naive transport: the model-replicated activation crosses the
                # pod DCN as-is.  The optimization_barrier pins the layout —
                # without it XLA's partitioner reshards before the permute and
                # re-gathers after, i.e. GSPMD performs the Atlas striping
                # automatically (see EXPERIMENTS.md §Perf B).
                y_send = _constrain_auto(y, P(None, None, None))
                y_send = jax.lax.optimization_barrier(y_send)
                buf_next = jax.lax.ppermute(
                    y_send, "pod", [(i, i + 1) for i in range(S - 1)]
                )
                buf_next = jax.lax.optimization_barrier(buf_next)
            buf_next = _constrain_auto(buf_next, P(None, None, None))

        # ---- loss on the last stage ----
        m_out = t - (S - 1)
        mo = jnp.clip(m_out, 0, n_micro - 1)
        with constraints_disabled():
            ce = parts.final_loss(rest, y, idx(t_mb, mo), idx(m_mb, mo))
        valid_out = jnp.logical_and(m_out >= 0, m_out < n_micro)
        is_last = my == (S - 1)
        ce = ce * valid_out.astype(jnp.float32) * is_last.astype(jnp.float32)
        return buf_next, (ce, aux)

    buf0 = jnp.zeros((mb, T, Dm), x_mb.dtype)
    _, (ces, auxs) = jax.lax.scan(rot, buf0, jnp.arange(steps))
    # psum over pod picks up the (single) last stage; psum over data
    # averages DP shards — its transpose is the DP gradient all-reduce.
    loss = jax.lax.psum(jnp.sum(ces), ("pod", "data")) / (n_micro * DP)
    aux = jax.lax.psum(jnp.sum(auxs), ("pod", "data")) / (n_micro * DP)
    return loss, aux
