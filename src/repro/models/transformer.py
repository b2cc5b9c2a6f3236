"""Model assembly: decoder / encoder / SSM / hybrid stacks with a uniform
functional API used by the trainer, the serving engine and the dry-run.

API (see ``build_model``):
    model.init(rng)                        -> params
    model.loss(params, batch)              -> (scalar loss, metrics dict)
    model.prefill(params, batch, cache)    -> (last-token logits, cache)
    model.decode_step(params, cache, tokens, pos) -> (logits, cache)
    model.cache_shape(batch, max_len)      -> pytree of ShapeDtypeStruct

Depth is always traversed with ``lax.scan`` over layer-stacked parameters
(leading ``L`` axis) so HLO size / compile time stay flat in num_layers —
the 88-layer granite dry-run compiles on a single-core host.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.modules import (
    ModelConfig,
    Params,
    cross_entropy_loss,
    dense,
    embed_init,
    ffn_apply,
    ffn_init,
    rmsnorm,
    rmsnorm_init,
    stack_layers,
)
from repro.parallel.sharding import constrain
from repro.scopes import ATTN, EMBED, FFN, LOSS_HEAD
from jax.sharding import PartitionSpec as P

LOSS_CHUNK = 256  # sequence chunk for the big-vocab CE (memory bound)


def _remat(fn: Callable, policy: str) -> Callable:
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def _shape_tree(spec: Dict[str, Tuple[Tuple[int, ...], Any]]):
    return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in spec.items()}


def _stack_shape_tree(spec, n: int):
    return {
        k: jax.ShapeDtypeStruct((n,) + s, d) for k, (s, d) in spec.items()
    }


# ---------------------------------------------------------------------------
# transformer (dense / moe / vlm / audio) blocks
# ---------------------------------------------------------------------------


def _block_init(key, cfg: ModelConfig) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    is_mla = cfg.mla is not None
    p = {
        "ln1": rmsnorm_init((cfg.d_model,)),
        "ln2": rmsnorm_init((cfg.d_model,)),
        "attn": attn.mla_init(k1, cfg) if is_mla else attn.gqa_init(k1, cfg),
    }
    if cfg.moe is not None:
        p["moe"] = moe_lib.moe_init(k2, cfg)
    else:
        p["ffn"] = ffn_init(k3, cfg.d_model, cfg.d_ff, cfg.ffn_activation, cfg.param_dtype)
    return p


def _block_apply(params, cfg: ModelConfig, x, positions, cache, gate=None):
    """One transformer block. Returns (x, new_cache, aux_loss).

    ``gate`` (scalar, optional) multiplies the residual deltas — used by
    the pipeline's identity-padding for *shared* blocks whose weights are
    not themselves zero-padded (zamba2)."""
    g = 1.0 if gate is None else gate.astype(cfg.dtype)
    with jax.named_scope(ATTN):
        h = rmsnorm(params["ln1"], x)
        if cfg.mla is not None:
            a, new_cache = attn.mla_apply(params["attn"], cfg, h, positions, cache)
        else:
            a, new_cache = attn.gqa_apply(params["attn"], cfg, h, positions, cache)
        x = x + a * g
    with jax.named_scope(FFN):
        h = rmsnorm(params["ln2"], x)
        if cfg.moe is not None:
            f, aux = moe_lib.moe_apply(params["moe"], cfg, h)
        else:
            f, aux = ffn_apply(params["ffn"], h, cfg.ffn_activation), jnp.float32(0.0)
        x = x + f * g
    x = constrain(x, P("data", None, None))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# the Model object
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    init: Callable[[jax.Array], Params]
    loss: Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]
    prefill: Callable[..., Tuple[jax.Array, Any]]
    decode_step: Callable[..., Tuple[jax.Array, Any]]
    cache_shape: Callable[[int, int], Any]


@dataclasses.dataclass
class PipelineParts:
    """Uniform per-layer view of a model for cross-pod pipeline parallelism
    (repro.parallel.pipeline).  ``layer`` must be structurally identical for
    every slice of the stacked layer params (lax.scan-compatible), so the
    same SPMD program can serve every pipeline stage."""

    layer_key: str  # params key holding the (L, ...) stacked layer params
    embed: Callable[[Params, Dict], Tuple[jax.Array, jax.Array]]  # -> x, positions
    layer: Callable[[Params, Params, jax.Array, jax.Array], Tuple[jax.Array, jax.Array]]
    # (layer_params, full_params, x, positions) -> (x, aux)
    final_loss: Callable[[Params, jax.Array, jax.Array, Optional[jax.Array]], jax.Array]
    # (full_params, x, targets, mask) -> scalar CE


def build_pipeline_parts(cfg: ModelConfig) -> PipelineParts:
    def embed(params, batch):
        if "embeds" in batch:
            x = batch["embeds"].astype(cfg.dtype)
        else:
            x = _embed_tokens(params, cfg, batch["tokens"])
        if "positions" in batch:
            positions = batch["positions"]
        elif cfg.mrope_sections is not None:
            pos2 = _default_positions(x.shape[:2])
            positions = jnp.broadcast_to(pos2[None], (3,) + pos2.shape)
        else:
            positions = _default_positions(x.shape[:2])
        return x, positions

    def final_loss(params, x, targets, mask):
        x = rmsnorm(params["final_norm"], x)
        return _lm_loss_chunked(cfg, x, _head_weight(params, cfg), targets, mask)

    if cfg.rwkv is not None:
        def layer(lp, params, x, positions):
            x, _ = rwkv_lib.rwkv6_apply(lp, cfg, x, None)
            return x, jnp.float32(0.0)

        return PipelineParts("layers", embed, layer, final_loss)

    if cfg.family == "hybrid":
        def layer(gp, params, x, positions):
            def mamba_body(hh, lp):
                y, _ = ssm_lib.mamba2_apply(lp["mamba"], cfg, rmsnorm(lp["ln"], hh), None)
                return hh + y, None

            x, _ = jax.lax.scan(mamba_body, x, gp["mamba"])
            x, _, aux = _block_apply(
                params["shared_attn"], cfg, x, positions, None, gate=gp["gate"]
            )
            return x, aux

        return PipelineParts("groups", embed, layer, final_loss)

    if cfg.family == "ssm":
        def layer(lp, params, x, positions):
            y, _ = ssm_lib.mamba2_apply(lp["mamba"], cfg, rmsnorm(lp["ln"], x), None)
            return x + y, jnp.float32(0.0)

        return PipelineParts("layers", embed, layer, final_loss)

    def layer(lp, params, x, positions):
        x, _, aux = _block_apply(lp, cfg, x, positions, None)
        return x, aux

    return PipelineParts("layers", embed, layer, final_loss)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.rwkv is not None:
        return _build_rwkv(cfg)
    if cfg.family == "hybrid":
        return _build_hybrid(cfg)
    if cfg.family == "ssm":
        return _build_ssm(cfg)
    return _build_transformer(cfg)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


@jax.named_scope(EMBED)
def _embed_tokens(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    e = params["embed"]  # (V, d)
    return jnp.take(e, tokens, axis=0).astype(cfg.dtype)


def _head_weight(params, cfg: ModelConfig) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T  # (d, V)
    return params["lm_head"]


@jax.named_scope(LOSS_HEAD)
def _lm_loss_chunked(cfg, x, w_head, labels, mask=None):
    """Next-token CE computed in sequence chunks to bound logits memory.

    x (B,T,d) (already final-normed); labels (B,T) are the *targets at each
    position* (pre-shifted by the caller); mask (B,T) optional.
    """
    B, T, _ = x.shape
    mask = jnp.ones((B, T), jnp.float32) if mask is None else mask.astype(jnp.float32)
    # the head enters in f32, so its gradient leaves in f32; the astype's
    # transpose casts it back to the head's own dtype
    return _chunked_ce(x, w_head.astype(jnp.float32), labels, mask)


def _to_chunks(a, chunk):
    """(B, T, ...) -> (nc, B, chunk, ...), zero-padding T up to nc * chunk."""
    B, T = a.shape[:2]
    nc = -(-T // chunk)
    a = jnp.pad(a, ((0, 0), (0, nc * chunk - T)) + ((0, 0),) * (a.ndim - 2))
    return a.reshape(B, nc, chunk, *a.shape[2:]).swapaxes(0, 1)


@jax.custom_vjp
def _chunked_ce(x, w_head, labels, mask):
    return _chunked_ce_fwd(x, w_head, labels, mask)[0]


def _chunked_ce_fwd(x, w_head, labels, mask):
    """The chunk scan also emits each chunk's logits cotangent in the compute
    dtype, so the backward needs neither f32 logits nor a one-hot."""
    T, V = x.shape[1], w_head.shape[-1]
    chunk = min(LOSS_CHUNK, T)
    xc = _to_chunks(x, chunk)
    w_c = w_head.astype(x.dtype)
    cnt = jnp.maximum(jnp.sum(mask), 1.0)

    def body(tot, inp):
        xi, li, mi = inp
        logits = jnp.einsum("btd,dv->btv", xi, w_c).astype(jnp.float32)
        logits = constrain(logits, P("data", None, "model"))
        logz = jax.nn.logsumexp(logits, axis=-1)
        onehot = (jnp.arange(V, dtype=li.dtype)[None, None, :] == li[..., None])
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        dlogits = (jnp.exp(logits - logz[..., None]) - onehot) * (mi / cnt)[..., None]
        dlogits = constrain(dlogits.astype(x.dtype), P("data", None, "model"))
        return tot + jnp.sum((logz - gold) * mi), dlogits

    tot, dlogits = jax.lax.scan(
        body, jnp.float32(0.0), (xc, _to_chunks(labels, chunk), _to_chunks(mask, chunk))
    )
    return tot / cnt, (x, w_c, dlogits)


def _chunked_ce_bwd(res, g):
    """The head gradient is one matmul over every token, accumulated in f32,
    not a per-chunk sum in the scan's carry. Padded rows are zero in both
    operands, so they add nothing."""
    x, w_c, dlogits = res
    B, T, d = x.shape
    nc, _, chunk, V = dlogits.shape
    # batch-major token rows, so that a batch sharded over "data" stays so
    dl = dlogits.swapaxes(0, 1).reshape(-1, V)
    xt = jnp.pad(x, ((0, 0), (0, nc * chunk - T), (0, 0))).reshape(-1, d)
    dx = g * jnp.einsum("kv,dv->kd", dl, w_c, preferred_element_type=jnp.float32)
    dw = g * jnp.einsum("kd,kv->dv", xt, dl, preferred_element_type=jnp.float32)
    return dx.reshape(B, nc * chunk, d)[:, :T].astype(x.dtype), dw, None, None


_chunked_ce.defvjp(_chunked_ce_fwd, _chunked_ce_bwd)


def _default_positions(tokens_shape, dtype=jnp.int32):
    B, T = tokens_shape
    return jnp.broadcast_to(jnp.arange(T, dtype=dtype)[None], (B, T))


# ---------------------------------------------------------------------------
# dense / moe / vlm / audio stack
# ---------------------------------------------------------------------------


def _build_transformer(cfg: ModelConfig) -> Model:
    L = cfg.num_layers

    def init(rng: jax.Array) -> Params:
        k_emb, k_layers, k_head = jax.random.split(rng, 3)
        p: Params = {
            "embed": embed_init(k_emb, (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
            "final_norm": rmsnorm_init((cfg.d_model,)),
            "layers": stack_layers(lambda k: _block_init(k, cfg), k_layers, L),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
        return p

    def backbone(params, x, positions, cache):
        """Scan the blocks. cache None or stacked (L, ...) pytree."""

        def body(carry, layer_in):
            h = carry
            lp, lc = layer_in
            h, new_c, aux = _block_apply(lp, cfg, h, positions, lc)
            return h, (new_c, aux)

        body = _remat(body, cfg.remat)
        x, (new_cache, auxs) = jax.lax.scan(body, x, (params["layers"], cache))
        return rmsnorm(params["final_norm"], x), new_cache, jnp.sum(auxs)

    def inputs_to_embeds(params, batch):
        if "embeds" in batch:  # vlm / audio precomputed frontend
            x = batch["embeds"].astype(cfg.dtype)
        else:
            x = _embed_tokens(params, cfg, batch["tokens"])
        if "positions" in batch:
            positions = batch["positions"]
        elif cfg.mrope_sections is not None:
            pos2 = _default_positions(x.shape[:2])
            positions = jnp.broadcast_to(pos2[None], (3,) + pos2.shape)
        else:
            positions = _default_positions(x.shape[:2])
        return x, positions

    def loss(params, batch):
        x, positions = inputs_to_embeds(params, batch)
        x = constrain(x, P("data", None, None))
        x, _, aux = backbone(params, x, positions, None)
        w_head = _head_weight(params, cfg)
        if cfg.causal:
            targets = batch.get("labels")
            if targets is None:  # standard next-token LM
                targets = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
                mask = jnp.ones_like(targets, jnp.float32).at[:, -1].set(0.0)
            else:
                mask = batch.get("mask")
            ce = _lm_loss_chunked(cfg, x, w_head, targets, mask)
        else:  # encoder (hubert): frame classification
            ce = _lm_loss_chunked(cfg, x, w_head, batch["labels"], batch.get("mask"))
        total = ce + aux
        return total, {"ce": ce, "aux": aux}

    def prefill(params, batch, cache):
        x, positions = inputs_to_embeds(params, batch)
        x, new_cache, _ = backbone(params, x, positions, cache)
        w_head = _head_weight(params, cfg)
        last = x[:, -1]
        logits = jnp.einsum("bd,dv->bv", last, w_head.astype(last.dtype))
        return logits.astype(jnp.float32), new_cache

    def decode_step(params, cache, tokens, pos):
        """tokens (B,) int32; pos (B,) int32 absolute positions."""
        x = _embed_tokens(params, cfg, tokens[:, None])
        positions = pos[:, None]
        if cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        x, new_cache, _ = backbone(params, x, positions, cache)
        w_head = _head_weight(params, cfg)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], w_head.astype(x.dtype))
        return logits.astype(jnp.float32), new_cache

    def cache_shape(batch: int, max_len: int):
        if cfg.mla is not None:
            per = attn.mla_cache_shape(cfg, batch, max_len)
        else:
            per = attn.gqa_cache_shape(cfg, batch, max_len)
        return _stack_shape_tree(per, L)

    return Model(cfg, init, loss, prefill, decode_step, cache_shape)


# ---------------------------------------------------------------------------
# pure SSM stack (mamba2) — not in the assigned pool standalone but used by
# tests and available via config
# ---------------------------------------------------------------------------


def _build_ssm(cfg: ModelConfig) -> Model:
    L = cfg.num_layers

    def layer_init(k):
        k1, _ = jax.random.split(k)
        return {"ln": rmsnorm_init((cfg.d_model,)), "mamba": ssm_lib.mamba2_init(k1, cfg)}

    def init(rng):
        k_emb, k_layers, k_head = jax.random.split(rng, 3)
        p = {
            "embed": embed_init(k_emb, (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
            "final_norm": rmsnorm_init((cfg.d_model,)),
            "layers": stack_layers(layer_init, k_layers, L),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
        return p

    def backbone(params, x, cache):
        def body(h, layer_in):
            lp, lc = layer_in
            y, new_c = ssm_lib.mamba2_apply(lp["mamba"], cfg, rmsnorm(lp["ln"], h), lc)
            return h + y, new_c

        body = _remat(body, cfg.remat)
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        return rmsnorm(params["final_norm"], x), new_cache

    def loss(params, batch):
        x = _embed_tokens(params, cfg, batch["tokens"])
        x, _ = backbone(params, x, None)
        targets = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
        mask = jnp.ones_like(targets, jnp.float32).at[:, -1].set(0.0)
        ce = _lm_loss_chunked(cfg, x, _head_weight(params, cfg), targets, mask)
        return ce, {"ce": ce}

    def _mk_zero_cache(batch):
        per = ssm_lib.mamba2_state_shape(cfg, batch)
        return {
            k: jnp.zeros((L,) + s, d) for k, (s, d) in per.items()
        }

    def prefill(params, batch, cache):
        x = _embed_tokens(params, cfg, batch["tokens"])
        x, new_cache = backbone(params, x, cache)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], _head_weight(params, cfg).astype(x.dtype))
        return logits.astype(jnp.float32), new_cache

    def decode_step(params, cache, tokens, pos):
        x = _embed_tokens(params, cfg, tokens[:, None])
        x, new_cache = backbone(params, x, cache)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], _head_weight(params, cfg).astype(x.dtype))
        return logits.astype(jnp.float32), new_cache

    def cache_shape(batch, max_len):
        return _stack_shape_tree(ssm_lib.mamba2_state_shape(cfg, batch), L)

    return Model(cfg, init, loss, prefill, decode_step, cache_shape)


# ---------------------------------------------------------------------------
# RWKV-6 stack
# ---------------------------------------------------------------------------


def _build_rwkv(cfg: ModelConfig) -> Model:
    L = cfg.num_layers

    def init(rng):
        k_emb, k_layers, k_head = jax.random.split(rng, 3)
        p = {
            "embed": embed_init(k_emb, (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
            "final_norm": rmsnorm_init((cfg.d_model,)),
            "layers": stack_layers(lambda k: rwkv_lib.rwkv6_init(k, cfg), k_layers, L),
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
        return p

    def backbone(params, x, cache):
        def body(h, layer_in):
            lp, lc = layer_in
            h, new_c = rwkv_lib.rwkv6_apply(lp, cfg, h, lc)
            return h, new_c

        body = _remat(body, cfg.remat)
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        return rmsnorm(params["final_norm"], x), new_cache

    def loss(params, batch):
        x = _embed_tokens(params, cfg, batch["tokens"])
        x, _ = backbone(params, x, None)
        targets = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
        mask = jnp.ones_like(targets, jnp.float32).at[:, -1].set(0.0)
        ce = _lm_loss_chunked(cfg, x, _head_weight(params, cfg), targets, mask)
        return ce, {"ce": ce}

    def prefill(params, batch, cache):
        x = _embed_tokens(params, cfg, batch["tokens"])
        x, new_cache = backbone(params, x, cache)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], _head_weight(params, cfg).astype(x.dtype))
        return logits.astype(jnp.float32), new_cache

    def decode_step(params, cache, tokens, pos):
        x = _embed_tokens(params, cfg, tokens[:, None])
        x, new_cache = backbone(params, x, cache)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], _head_weight(params, cfg).astype(x.dtype))
        return logits.astype(jnp.float32), new_cache

    def cache_shape(batch, max_len):
        return _stack_shape_tree(rwkv_lib.rwkv6_state_shape(cfg, batch), L)

    return Model(cfg, init, loss, prefill, decode_step, cache_shape)


# ---------------------------------------------------------------------------
# hybrid (zamba2): mamba2 backbone + one *shared* transformer block applied
# every ``attn_period`` layers
# ---------------------------------------------------------------------------


def _build_hybrid(cfg: ModelConfig) -> Model:
    assert cfg.attn_period and cfg.num_layers % cfg.attn_period == 0
    groups = cfg.num_layers // cfg.attn_period
    m_per = cfg.attn_period - 1  # mamba layers per group

    def mamba_layer_init(k):
        return {"ln": rmsnorm_init((cfg.d_model,)), "mamba": ssm_lib.mamba2_init(k, cfg)}

    def init(rng):
        k_emb, k_m, k_a = jax.random.split(rng, 3)
        keys = jax.random.split(k_m, groups * m_per)

        def group_init(kg):
            return jax.vmap(mamba_layer_init)(kg)

        mk = keys.reshape(groups, m_per, -1)
        k_a, k_head = jax.random.split(k_a)
        p = {
            "embed": embed_init(k_emb, (cfg.vocab_size, cfg.d_model), cfg.param_dtype),
            "final_norm": rmsnorm_init((cfg.d_model,)),
            "groups": {
                "mamba": jax.vmap(group_init)(mk),  # (G, M, ...)
                # per-group gate on the shared block's residual deltas; a
                # zero-padded group becomes an exact identity (pipeline)
                "gate": jnp.ones((groups,), jnp.float32),
            },
            "shared_attn": _block_init(k_a, cfg),  # single shared block
        }
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size), cfg.param_dtype)
        return p

    def backbone(params, x, positions, cache):
        """cache: {"mamba": (G,M,...), "attn": (G,...)} or None."""
        shared = params["shared_attn"]

        def group_body(h, group_in):
            gp, gc_m, gc_a = group_in

            def mamba_body(hh, m_in):
                lp, lc = m_in
                y, new_c = ssm_lib.mamba2_apply(lp["mamba"], cfg, rmsnorm(lp["ln"], hh), lc)
                return hh + y, new_c

            h, new_mc = jax.lax.scan(mamba_body, h, (gp["mamba"], gc_m))
            h, new_ac, _aux = _block_apply(shared, cfg, h, positions, gc_a, gate=gp["gate"])
            return h, (new_mc, new_ac)

        group_body = _remat(group_body, cfg.remat)
        gc_m = cache["mamba"] if cache is not None else None
        gc_a = cache["attn"] if cache is not None else None
        x, (new_m, new_a) = jax.lax.scan(group_body, x, (params["groups"], gc_m, gc_a))
        new_cache = {"mamba": new_m, "attn": new_a} if cache is not None else None
        return rmsnorm(params["final_norm"], x), new_cache

    def loss(params, batch):
        x = _embed_tokens(params, cfg, batch["tokens"])
        positions = _default_positions(batch["tokens"].shape)
        x, _ = backbone(params, x, positions, None)
        targets = jnp.pad(batch["tokens"][:, 1:], ((0, 0), (0, 1)))
        mask = jnp.ones_like(targets, jnp.float32).at[:, -1].set(0.0)
        ce = _lm_loss_chunked(cfg, x, _head_weight(params, cfg), targets, mask)
        return ce, {"ce": ce}

    def prefill(params, batch, cache):
        x = _embed_tokens(params, cfg, batch["tokens"])
        positions = _default_positions(batch["tokens"].shape)
        x, new_cache = backbone(params, x, positions, cache)
        logits = jnp.einsum("bd,dv->bv", x[:, -1], _head_weight(params, cfg).astype(x.dtype))
        return logits.astype(jnp.float32), new_cache

    def decode_step(params, cache, tokens, pos):
        x = _embed_tokens(params, cfg, tokens[:, None])
        x, new_cache = backbone(params, x, pos[:, None], cache)
        logits = jnp.einsum("bd,dv->bv", x[:, 0], _head_weight(params, cfg).astype(x.dtype))
        return logits.astype(jnp.float32), new_cache

    def cache_shape(batch, max_len):
        m_per_shape = ssm_lib.mamba2_state_shape(cfg, batch)
        a_shape = attn.gqa_cache_shape(cfg, batch, max_len)
        return {
            "mamba": {
                k: jax.ShapeDtypeStruct((groups, m_per) + s, d)
                for k, (s, d) in m_per_shape.items()
            },
            "attn": {
                k: jax.ShapeDtypeStruct((groups,) + s, d) for k, (s, d) in a_shape.items()
            },
        }

    return Model(cfg, init, loss, prefill, decode_step, cache_shape)
