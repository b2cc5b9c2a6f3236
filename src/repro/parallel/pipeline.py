"""Cross-pod pipeline parallelism — the paper's "PP across DCs" mapped to
TPU (DESIGN.md §2).

The ``pod`` mesh axis carries pipeline stages; ``data`` carries DP within a
pod; ``model`` carries TP.  Only the layer stack is pipelined: a
circular-rotation microbatch pipeline inside a *partial-auto* shard_map,
manual over {pod, data} (``lax.ppermute`` moves stage-boundary activations
across the inter-pod DCN; per-data-shard token work is local, which also
sidesteps XLA SPMD partitioner failures around MoE gather/scatter in manual
subgroups), while GSPMD keeps handling the ``model`` axis (TP)
automatically.  Autodiff through the scan+ppermute yields the
reversed-permutation backward pipeline for free.

Into the manual region go the layers, each stage its own slice, and the
other parameters the layers read (zamba2's ``shared_attn``): their
gradients are summed over ``data`` (DP) and, for the shared ones, over
``pod``.  The embedding lookup before the stages and the final norm and
loss head after them (``PipelineParts.io_keys``) run once in the GSPMD
region around it, as in the plain model: a parameter taken into the
region whole would have its gradient summed over ``pod``, which for the
tables only stage 0 and the last stage read adds zeros.  What crosses
``pod`` for them is the activations: the last stage's outputs on their way
to the head, and the input's cotangent back.

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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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
    assert not cfg.tie_embeddings, "pipeline requires untied embeddings"
    parts = build_pipeline_parts(cfg)
    S = mesh.shape["pod"]
    DP = mesh.shape["data"]

    def loss_fn(params: Dict, batch: Dict) -> jax.Array:
        # a no-op forward whose transpose lays the gradients out as the
        # parameters: left to GSPMD, the norm scales' come back split
        # over ``model`` and every later step compiles a second program
        params = jax.lax.with_sharding_constraint(
            params, make_param_shardings(params, mesh, fsdp=fsdp))
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

        # the embedding is looked up here, in the GSPMD region, and enters
        # the stages in f32: its cotangent leaves them summed over ``pod``
        # (zero but on stage 0), a sum XLA's CPU compiler fails on in bf16
        if "embeds" in batch:
            x_mb = batch["embeds"].reshape(n_micro, mb, T, -1)
        else:
            x_mb = parts.embed(params, batch["tokens"].reshape(n_micro, mb, T))
        x_mb = x_mb.astype(jnp.float32)

        layers = pad_layer_stack(params[parts.layer_key], S)
        # only what the layers read enters the region manual over ``pod``:
        # the gradient of a parameter every stage reads there is summed
        # over ``pod``, which for the embedding and head would add zeros
        shared = {k: v for k, v in params.items()
                  if k != parts.layer_key and k not in parts.io_keys}

        inner = functools.partial(
            _pipeline_inner, parts=parts, cfg=cfg, S=S, DP=DP,
            n_micro=n_micro, boundary=boundary,
        )
        sm = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                jax.tree.map(lambda _: P("pod"), layers),
                jax.tree.map(lambda _: P(), shared),
                P(None, "data", None, None),
                pos_spec,
            ),
            out_specs=(P("pod", None, "data", None, None), P()),
            # partial-auto: GSPMD keeps handling TP on ``model``
            axis_names={"pod", "data"},
            check_vma=False,
        )
        with jax.named_scope(PIPELINE):
            ys, aux = sm(layers, shared, x_mb, pos_mb)
            with jax.named_scope(STAGE_BOUNDARY):
                # the last stage's outputs, to every pod: one sum over
                # ``pod`` forward, and a local select backward (``ys[S-1]``
                # would permute both ways)
                last = (jnp.arange(S) == S - 1)[:, None, None, None, None]
                h = jnp.sum(jnp.where(last, ys, 0), axis=0)

        # the loss is the mean over microbatches and data shards of each
        # one's masked mean CE: weights that sum to at most 1 turn the
        # head's masked mean (over max(sum, 1)) into that mean
        m_g = mask.astype(jnp.float32).reshape(n_micro, DP, mb // DP, T)
        cnt = jnp.maximum(jnp.sum(m_g, axis=(2, 3), keepdims=True), 1.0)
        w_mb = (m_g / (cnt * (n_micro * DP))).reshape(n_micro, mb, T)
        # microbatch-minor rows keep a batch split over ``data`` split;
        # the head runs whole on every pod, so its gradient needs no sum
        # over ``pod`` (left to GSPMD, it splits the head over ``pod``)
        rows = lambda a: a.swapaxes(0, 1).reshape(B, *a.shape[2:])
        x = jax.lax.with_sharding_constraint(rows(h), NamedSharding(mesh, P("data")))
        t_mb = targets.reshape(n_micro, mb, T)
        ce = parts.final_loss(params, x, rows(t_mb), rows(w_mb))
        return ce + aux

    return loss_fn


def _constrain_auto(x, spec):
    """``with_sharding_constraint`` over the axes the manual region leaves
    automatic (``model``), on the ambient abstract mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(mesh, spec))


def _pipeline_inner(
    layers, shared, x_mb, pos_mb, *, parts, cfg, S, DP, n_micro, boundary,
):
    """Manual over {pod, data}: ``layers`` is this stage's (L/S, ...) slice,
    ``shared`` the other parameters the layers read; the microbatches are
    this data shard's. Returns this stage's outputs of the rotations in
    which the last stage finishes microbatch 0, 1, ..., with a leading
    stage axis, and the layers' aux loss."""
    my = jax.lax.axis_index("pod")  # this pod's stage index
    steps = n_micro + S - 1
    with jax.named_scope(EMBED):
        x_mb = x_mb.astype(cfg.dtype)
    mb, T, Dm = x_mb.shape[1:]

    def stage_fn(x, positions):
        def body(h, lp):
            # the layers' own sharding constraints stay off here (XLA's
            # partitioner rejects some in the manual subgroup: MoE
            # dispatch) — GSPMD still propagates the model-axis (TP)
            # shardings from the parameters.
            with constraints_disabled():
                h, aux = parts.layer(lp, shared, h, positions)
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
        return buf_next, (y, aux)

    buf0 = jnp.zeros((mb, T, Dm), x_mb.dtype)
    _, (ys, auxs) = jax.lax.scan(rot, buf0, jnp.arange(steps))
    # the layers' aux loss: summed over the stages, averaged over the
    # microbatches and data shards
    aux = jax.lax.psum(jnp.sum(auxs), ("pod", "data")) / (n_micro * DP)
    return ys[None, S - 1:], aux
