"""jit'd public wrappers around the Pallas kernels.

These accept the model-layer layouts ((B, T, H, D) etc.), reshape to the
kernel layouts and run the kernels compiled, except on the CPU backend,
where they run in interpret mode.  A shape that does not divide into the
kernel's blocks raises ``ValueError``; callers that must handle such
shapes check ``flash_attention_fits``/``decode_attention_fits`` first and
stay on XLA.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rms
from repro.kernels import wkv6 as _wkv


FLASH_BLOCK = 128
DECODE_BLOCK_KV = 256


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _check_blocks(kernel: str, shape, dims, blocks) -> None:
    if any(n % b for n, b in zip(dims, blocks)):
        raise ValueError(
            f"{kernel}: shape {tuple(shape)} does not divide into blocks "
            f"{tuple(blocks)} along dims {tuple(dims)}"
        )


def flash_attention_fits(T: int, S: int) -> bool:
    """True when the default flash blocks tile a (T, S) attention."""
    return T % min(FLASH_BLOCK, T) == 0 and S % min(FLASH_BLOCK, S) == 0


def decode_attention_fits(S: int) -> bool:
    """True when the default decode block tiles an S-slot cache."""
    return S % min(DECODE_BLOCK_KV, S) == 0


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_kv"))
def flash_attention(
    q: jax.Array,  # (B, T, Hq, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = FLASH_BLOCK,
    block_kv: int = FLASH_BLOCK,
) -> jax.Array:
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else D**-0.5
    bq, bkv = min(block_q, T), min(block_kv, S)
    _check_blocks("flash_attention", q.shape, (T, S), (bq, bkv))
    group = Hq // Hkv
    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, T, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    o = _fa.flash_attention_bhsd(
        qr, kr, vr, group=group, scale=scale, causal=causal,
        block_q=bq, block_kv=bkv, interpret=_interpret(),
    )
    return o.reshape(B, Hq, T, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "scale", "block_kv"))
def decode_attention(
    q: jax.Array,  # (B, 1, Hq, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,
    q_pos: jax.Array,  # (B, 1)
    kv_pos: jax.Array,  # (B, S)
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_kv: int = DECODE_BLOCK_KV,
) -> jax.Array:
    B, T, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert T == 1
    scale = scale if scale is not None else D**-0.5
    bkv = min(block_kv, S)
    _check_blocks("decode_attention", k.shape, (S,), (bkv,))
    group = Hq // Hkv
    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, 1, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    qp = jnp.repeat(q_pos, Hq, axis=0).reshape(B * Hq, 1)
    kp = jnp.repeat(kv_pos, Hkv, axis=0).reshape(B * Hkv, S)
    o = _dec.decode_attention_bhsd(
        qr, kr, vr, qp, kp, group=group, scale=scale,
        window=window or 0, block_kv=bkv, interpret=_interpret(),
    )
    return o.reshape(B, Hq, 1, D).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6, block_rows: int = 256):
    shape = x.shape
    N = 1
    for s in shape[:-1]:
        N *= s
    xr = x.reshape(N, shape[-1])
    br = min(block_rows, N)
    _check_blocks("rmsnorm", shape, (N,), (br,))
    o = _rms.rmsnorm_rows(xr, scale, eps=eps, block_rows=br, interpret=_interpret())
    return o.reshape(shape)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(
    r: jax.Array,  # (B, T, H, D)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,
    u: jax.Array,  # (H, D)
    *,
    chunk: int = 64,
) -> jax.Array:
    B, T, H, D = r.shape
    c = min(chunk, T)
    _check_blocks("wkv6", r.shape, (T,), (c,))
    tr = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    ub = jnp.tile(u, (B, 1))  # (B*H, D)
    o = _wkv.wkv6_bhtd(
        tr(r), tr(k), tr(v), tr(logw.astype(jnp.float32)), ub, chunk=c,
        interpret=_interpret(),
    )
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)
