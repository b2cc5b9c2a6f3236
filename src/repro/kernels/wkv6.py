"""RWKV-6 WKV chunked recurrence — Pallas kernel.

Per (batch·head) row, the kv axis of the grid walks chunks *sequentially*
and carries the (D_k × D_v) state in VMEM scratch — the TPU-native shape
of a linear-attention recurrence (state never leaves VMEM between chunks).

    S_t   = diag(w_t) S_{t-1} + k_tᵀ v_t
    out_t = r_t · (S_{t-1} + diag(u) k_tᵀ v_t)

Grid: (B·H, num_chunks).  Within a chunk the intra-term is an MXU-friendly
masked (chunk × chunk) matmul on decay-rescaled r/k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 matmuls at full precision: the recurrence compounds rounding error
_HIGHEST = jax.lax.Precision.HIGHEST


def _wkv6_kernel(
    r_ref,  # (1, C, D)
    k_ref,
    v_ref,
    lw_ref,  # (1, C, D) log decay (<= 0), f32
    u_ref,  # (1, 1, D)
    o_ref,  # (1, C, D)
    state_ref,  # scratch (D, D) f32  [key-dim x value-dim]
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)  # (C, D)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, D)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive prefix sum over time as a lower-triangular matmul (MXU)
    tri = (t_idx >= s_idx).astype(jnp.float32)
    lcum_inc = jnp.dot(tri, lw, precision=_HIGHEST)
    lcum = lcum_inc - lw  # exclusive
    ltot = lcum_inc[chunk - 1 :]  # (1, D)

    r_sc = r * jnp.exp(lcum)
    k_sc = k * jnp.exp(-lcum_inc)
    scores = jnp.dot(r_sc, k_sc.T, precision=_HIGHEST)  # (C, C)
    scores = jnp.where(t_idx > s_idx, scores, 0.0)  # strictly causal
    y = jnp.dot(scores, v, precision=_HIGHEST)
    # current-token bonus
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)  # (C, 1)
    y = y + diag * v
    # inter-chunk from carried state
    y = y + jnp.dot(r_sc, state_ref[...], precision=_HIGHEST)

    o_ref[0] = y.astype(o_ref.dtype)

    # update state: S = diag(prod w) S + sum_s exp(ltot - lcum_inc_s) k_s v_sᵀ
    kw = k * jnp.exp(ltot - lcum_inc)
    state_ref[...] = state_ref[...] * jnp.exp(ltot).T + jnp.dot(
        kw.T, v, precision=_HIGHEST
    )


def wkv6_bhtd(
    r: jax.Array,  # (BH, T, D)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (BH, T, D), <= 0, f32
    u: jax.Array,  # (BH, D)
    *,
    chunk: int = 64,
    interpret: bool = True,
) -> jax.Array:
    BH, T, D = r.shape
    chunk = min(chunk, T)
    assert T % chunk == 0
    nc = T // chunk
    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, 1, D), lambda i, c: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, D), lambda i, c: (i, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), r.dtype),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u[:, None, :])
