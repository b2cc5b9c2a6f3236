"""Attention flavours: MHA/GQA/MQA + RoPE / M-RoPE / sliding window / MLA.

Everything is expressed over explicit position ids so the same code path
serves training (q_pos == kv_pos == arange), prefill (same) and single-token
decode against a (possibly ring-buffered sliding-window) KV cache.

Layout conventions:
  q           (B, T, Hq,  Dh)
  k, v        (B, S, Hkv, Dh)
  kv cache    {"k": (B, S, Hkv, Dh), "v": ..., "pos": (B, S) int32 (-1 = empty)}
  MLA cache   {"ckv": (B, S, kv_lora), "k_rope": (B, S, rope_dim), "pos": (B, S)}
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.modules import (
    MLAConfig,
    ModelConfig,
    Params,
    dense,
    dense_init,
)

NEG_INF = -2.0**30

# implementation switch: "xla" (pure jnp, the oracle) or "pallas"
# (repro.kernels flash/decode kernels; interpret-mode on CPU).
_IMPL = "xla"


def set_attention_impl(impl: str) -> None:
    global _IMPL
    assert impl in ("xla", "pallas"), impl
    _IMPL = impl


def get_attention_impl() -> str:
    return _IMPL


@contextlib.contextmanager
def force_impl(impl: str):
    """Pin the attention impl for the duration (trace-time decision).

    The pallas flash kernel ignores q_pos/kv_pos, so any caller whose
    positions are not dense 0..T-1 (e.g. serving's left-padded prefill,
    pad slots at position -1) must trace under ``force_impl("xla")`` to
    keep the position mask."""
    global _IMPL
    prev = _IMPL
    set_attention_impl(impl)
    try:
        yield
    finally:
        _IMPL = prev


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def _rope_angles(positions: jax.Array, head_dim: int, theta: float) -> jax.Array:
    """positions (...,) -> angles (..., head_dim // 2) in f32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * freqs


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x (B, T, H, D), positions (B, T) -> rotated x (rotate-half form)."""
    ang = _rope_angles(positions, x.shape[-1], theta)[..., None, :]  # (B,T,1,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,
    sections: Tuple[int, int, int],
    theta: float,
) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions: (3, B, T) — temporal / height / width position ids (text
    tokens carry (t, t, t)).  ``sections`` splits the *half* dimension;
    section i takes its angles from positions[i].
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang_all = positions.astype(jnp.float32)[..., None] * freqs  # (3,B,T,half)
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        parts.append(ang_all[i, ..., start : start + sec])
        start += sec
    ang = jnp.concatenate(parts, axis=-1)[..., None, :]  # (B,T,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# scaled dot-product attention over explicit positions
# ---------------------------------------------------------------------------


def sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    *,
    causal: bool,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention. q (B,T,Hq,D); k/v (B,S,Hkv,Dv-compatible).

    q_pos (B, T), kv_pos (B, S); kv_pos < 0 marks empty cache slots.
    """
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = scale if scale is not None else D**-0.5

    if _IMPL == "pallas":
        from repro.kernels import ops as kops

        # shapes the kernels' blocks do not tile stay on XLA
        if (T > 1 and window is None and q_pos.shape == kv_pos.shape
                and kops.flash_attention_fits(T, S)):
            return kops.flash_attention(q, k, v, causal=causal, scale=scale)
        if T == 1 and kops.decode_attention_fits(S):
            return kops.decode_attention(
                q, k, v, q_pos, kv_pos, window=window, scale=scale
            )

    qf = (q.astype(jnp.float32) * scale).reshape(B, T, Hkv, G, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", qf, k.astype(jnp.float32))
    mask = kv_pos[:, None, :] >= 0  # (B, T=1-bcast, S)
    if causal:
        mask = mask & (kv_pos[:, None, :] <= q_pos[:, :, None])
    if window is not None:
        mask = mask & (kv_pos[:, None, :] > q_pos[:, :, None] - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, T, Hq, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (with optional cache)
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ModelConfig) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), cfg.param_dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), cfg.param_dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), cfg.param_dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), cfg.param_dtype),
    }


def _rotate(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    if cfg.mrope_sections is not None:
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


def gqa_apply(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """x (B, T, d).  positions (B, T) or (3, B, T) for M-RoPE.

    With ``cache`` (decode / incremental prefill): writes the new K/V at
    ring slots ``pos % S`` and attends against the whole cache.
    Returns (out, new_cache).
    """
    B, T, d = x.shape
    hd = cfg.resolved_head_dim
    q = dense(params["wq"], x).reshape(B, T, cfg.num_heads, hd)
    k = dense(params["wk"], x).reshape(B, T, cfg.num_kv_heads, hd)
    v = dense(params["wv"], x).reshape(B, T, cfg.num_kv_heads, hd)
    scalar_pos = positions if positions.ndim == 2 else positions[0]
    q = _rotate(cfg, q, positions)
    k = _rotate(cfg, k, positions)

    if cache is None:
        out = sdpa(q, k, v, scalar_pos, scalar_pos, causal=cfg.causal, window=cfg.window)
        new_cache = None
    else:
        S = cache["k"].shape[1]
        # attention itself runs against full-resolution K/V when prefit
        # (T > 1); cache writes keep only the last S tokens (ring buffer),
        # whose slots pos % S are distinct because positions are contiguous.
        if T > 1:
            out = sdpa(q, k, v, scalar_pos, scalar_pos, causal=True, window=cfg.window)
            kw, vw, pw = k[:, -S:], v[:, -S:], scalar_pos[:, -S:]
        else:
            kw, vw, pw = k, v, scalar_pos
        slots = pw % S  # (B, <=S)
        bidx = jnp.arange(B)[:, None]
        ck = cache["k"].at[bidx, slots].set(kw)
        cv = cache["v"].at[bidx, slots].set(vw)
        cpos = cache["pos"].at[bidx, slots].set(pw)
        if T == 1:
            out = sdpa(q, ck, cv, scalar_pos, cpos, causal=True, window=cfg.window)
        new_cache = {"k": ck, "v": cv, "pos": cpos}

    out = out.reshape(B, T, cfg.num_heads * hd)
    return dense(params["wo"], out), new_cache


def gqa_cache_shape(cfg: ModelConfig, batch: int, max_len: int):
    """Per-layer cache shapes. Sliding window bounds the ring size."""
    S = min(max_len, cfg.window) if cfg.window else max_len
    hd = cfg.resolved_head_dim
    return {
        "k": ((batch, S, cfg.num_kv_heads, hd), cfg.dtype),
        "v": ((batch, S, cfg.num_kv_heads, hd), cfg.dtype),
        "pos": ((batch, S), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------


def mla_init(key, cfg: ModelConfig) -> Params:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 5)
    return {
        # queries: full-rank (V2-Lite has no q-LoRA)
        "wq": dense_init(ks[0], (d, H * (m.qk_nope_head_dim + m.qk_rope_head_dim)), cfg.param_dtype),
        # down-projection to the shared latent + decoupled rope key
        "w_dkv": dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), cfg.param_dtype),
        # up-projections from the latent
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, H * m.qk_nope_head_dim), cfg.param_dtype),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, H * m.v_head_dim), cfg.param_dtype),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), cfg.param_dtype),
    }


def mla_apply(
    params: Params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[Params] = None,
) -> Tuple[jax.Array, Optional[Params]]:
    """MLA with latent-space ("weight absorbed") attention.

    The cache stores only the compressed latent (kv_lora_rank) plus the
    shared rope key — the paper-relevant property for decode_32k/long_500k
    memory.  Scores are computed in latent space:
        score = (q_nope · W_uk)ᵀ c_kv + q_ropeᵀ k_rope
        out   = (probs · c_kv) · W_uv
    """
    m = cfg.mla
    B, T, _ = x.shape
    H = cfg.num_heads
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    scale = (dn + dr) ** -0.5

    q = dense(params["wq"], x).reshape(B, T, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    # absorb W_uk into the query:  (B,T,H,dn) x (lora,H,dn) -> (B,T,H,lora)
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, dn)
    q_lat = jnp.einsum("bthd,rhd->bthr", q_nope.astype(jnp.float32), w_uk.astype(jnp.float32))

    dkv = dense(params["w_dkv"], x)
    ckv, k_rope = dkv[..., : m.kv_lora_rank], dkv[..., m.kv_lora_rank :]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]

    if cache is None:
        kv_pos = positions
        new_cache = None
        ckv_all, k_rope_all = ckv, k_rope
    else:
        S = cache["ckv"].shape[1]
        slots = positions % S
        bidx = jnp.arange(B)[:, None]
        ckv_all = cache["ckv"].at[bidx, slots].set(ckv)
        k_rope_all = cache["k_rope"].at[bidx, slots].set(k_rope)
        kv_pos = cache["pos"].at[bidx, slots].set(positions)
        new_cache = {"ckv": ckv_all, "k_rope": k_rope_all, "pos": kv_pos}
    kv_pos_arr = kv_pos

    scores = jnp.einsum("bthr,bsr->bhts", q_lat, ckv_all.astype(jnp.float32))
    scores += jnp.einsum(
        "bthd,bsd->bhts", q_rope.astype(jnp.float32), k_rope_all.astype(jnp.float32)
    )
    scores *= scale
    mask = kv_pos_arr[:, None, :] >= 0
    mask = mask & (kv_pos_arr[:, None, :] <= positions[:, :, None])
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    lat_out = jnp.einsum("bhts,bsr->bthr", probs, ckv_all.astype(jnp.float32))
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bthr,rhv->bthv", lat_out, w_uv.astype(jnp.float32))
    out = out.reshape(B, T, H * m.v_head_dim).astype(x.dtype)
    return dense(params["wo"], out), new_cache


def mla_cache_shape(cfg: ModelConfig, batch: int, max_len: int):
    m = cfg.mla
    S = min(max_len, cfg.window) if cfg.window else max_len
    return {
        "ckv": ((batch, S, m.kv_lora_rank), cfg.dtype),
        "k_rope": ((batch, S, m.qk_rope_head_dim), cfg.dtype),
        "pos": ((batch, S), jnp.int32),
    }
