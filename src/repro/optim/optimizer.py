"""Optimizer substrate: AdamW + warmup-cosine schedule + global-norm clip +
gradient accumulation.  No optax in this environment — states are plain
pytrees, shard like their parameters, and work under jit/pjit unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.scopes import OPTIMIZER

Params = Any


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    peak_lr: float = 3e-4
    min_lr_ratio: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


class OptState(NamedTuple):
    step: jax.Array
    mu: Params
    nu: Params


def init_opt_state(params: Params) -> OptState:
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return OptState(
        step=jnp.zeros((), jnp.int32),
        mu=jax.tree.map(zeros, params),
        nu=jax.tree.map(zeros, params),
    )


def lr_at(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = cfg.peak_lr * step / max(cfg.warmup_steps, 1)
    prog = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0
    )
    cos = cfg.peak_lr * (
        cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    )
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def global_norm(tree: Params) -> jax.Array:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))
    )


def _decay_mask(path: Tuple, leaf) -> bool:
    """Weight decay on matrices only (no norms/bias/scalars)."""
    return leaf.ndim >= 2


def adamw_update(
    cfg: OptimizerConfig, grads: Params, params: Params, state: OptState
) -> Tuple[Params, OptState, Dict[str, jax.Array]]:
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    step = state.step + 1
    lr = lr_at(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    flat_p, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_g = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state.mu)
    flat_nu = jax.tree.leaves(state.nu)

    new_p, new_mu, new_nu = [], [], []
    for (path, p), g, mu, nu in zip(flat_p, flat_g, flat_mu, flat_nu):
        g32 = g.astype(jnp.float32) * scale
        mu = cfg.b1 * mu + (1 - cfg.b1) * g32
        nu = cfg.b2 * nu + (1 - cfg.b2) * jnp.square(g32)
        upd = (mu / b1c) / (jnp.sqrt(nu / b2c) + cfg.eps)
        if _decay_mask(path, p):
            upd = upd + cfg.weight_decay * p.astype(jnp.float32)
        new_p.append((p.astype(jnp.float32) - lr * upd).astype(p.dtype))
        new_mu.append(mu)
        new_nu.append(nu)

    unflatten = lambda leaves: jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), leaves
    )
    return (
        unflatten(new_p),
        OptState(step, unflatten(new_mu), unflatten(new_nu)),
        {"grad_norm": gnorm, "lr": lr},
    )


def make_train_step(
    loss_fn: Callable[[Params, Dict], Any],
    opt_cfg: OptimizerConfig,
    *,
    loss_has_metrics: bool = True,
    accum_steps: int = 1,
):
    """train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    accum_steps > 1 splits the batch on dim0 and accumulates gradients in
    f32 (the paper's minibatch = microbatches × this, orthogonal to the
    pipeline's own microbatching).
    """

    def scalar_loss(params, batch):
        out = loss_fn(params, batch)
        if loss_has_metrics:
            loss, metrics = out
        else:
            loss, metrics = out, {}
        return loss, metrics

    grad_fn = jax.value_and_grad(scalar_loss, has_aux=True)

    def train_step(params, opt_state: OptState, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            split = lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:])
            batches = jax.tree.map(split, batch)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, _), g = grad_fn(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / accum_steps, g_acc, g
                )
                return (g_acc, l_acc + l / accum_steps), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(acc_body, (g0, 0.0), batches)
            metrics = {}
        with jax.named_scope(OPTIMIZER):
            params, opt_state, om = adamw_update(opt_cfg, grads, params, opt_state)
        metrics = {**metrics, **om, "loss": loss}
        return params, opt_state, metrics

    return train_step
