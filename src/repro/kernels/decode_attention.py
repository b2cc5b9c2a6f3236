"""Single-token (decode) attention over a long KV cache — Pallas kernel.

One query per (batch, head); the kernel streams KV blocks through VMEM and
keeps the online-softmax accumulators in scratch.  Validity/causality/
sliding-window masking is driven by the cache's per-slot position array
(ring-buffer caches leave ``pos`` in arbitrary slot order, so masking by
value — not by index — is required).

Grid: (B·H, kv_blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0**30


def _decode_kernel(
    q_ref,  # (1, 1, D)
    k_ref,  # (1, bkv, D)
    v_ref,
    kvpos_ref,  # (1, 1, bkv)
    qpos_ref,  # (1, 1, 1)
    o_ref,  # (1, 1, D)
    acc_ref,  # (1, D)
    m_ref,  # (1, 1)
    l_ref,  # (1, 1)
    *,
    scale: float,
    window: int,
    num_kv_blocks: int,
):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # every operand stays 2-D (rows x lanes), as the TPU vector layout wants
    q = q_ref[0].astype(jnp.float32) * scale  # (1, D)
    k = k_ref[0].astype(jnp.float32)  # (bkv, D)
    v = v_ref[0].astype(jnp.float32)
    kv_pos = kvpos_ref[0]  # (1, bkv)
    q_pos = qpos_ref[0]  # (1, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, bkv)
    valid = (kv_pos >= 0) & (kv_pos <= q_pos)
    if window > 0:
        valid = valid & (kv_pos > q_pos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(p, v)
    m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def decode_attention_bhsd(
    q: jax.Array,  # (BH, 1, D)
    k: jax.Array,  # (BKv, S, D)
    v: jax.Array,
    q_pos: jax.Array,  # (BH, 1) int32
    kv_pos: jax.Array,  # (BKv, S) int32
    *,
    group: int,
    scale: float,
    window: int = 0,
    block_kv: int = 256,
    interpret: bool = True,
) -> jax.Array:
    BH, _, D = q.shape
    S = k.shape[1]
    block_kv = min(block_kv, S)
    assert S % block_kv == 0
    nkv = S // block_kv

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, num_kv_blocks=nkv
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, D), lambda i, k_: (i, 0, 0)),
            pl.BlockSpec((1, block_kv, D), lambda i, k_, g=group: (i // g, k_, 0)),
            pl.BlockSpec((1, block_kv, D), lambda i, k_, g=group: (i // g, k_, 0)),
            pl.BlockSpec((1, 1, block_kv), lambda i, k_, g=group: (i // g, 0, k_)),
            pl.BlockSpec((1, 1, 1), lambda i, k_: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda i, k_: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, kv_pos[:, None, :], q_pos[:, :, None])
