"""Serving engine: KV-cache management, batched prefill/decode, and the
Splitwise-style prefill/decode split that BubbleTea builds on (paper §5).

Roles:
  * ``ServingEngine`` — owns params + a ring of KV caches, runs batched
    ``prefill`` and ``decode_step`` (the jit'd model functions), applies
    greedy/temperature sampling, and tracks per-request TTFT/TBT.
  * ``SplitwiseCluster`` — two engines sharing weights: "prefill side"
    (in BubbleTea's case: training GPUs during bubbles) hands the KV
    cache to the "decode side" (dedicated decode GPUs in the same DC).
    On CPU the "transfer" is a pytree copy; its simulated WAN/ICI cost is
    accounted by repro.core.bubbletea's latency model.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention
from repro.models.modules import ModelConfig
from repro.models.transformer import Model, build_model


def zeros_cache(model: Model, batch: int, max_len: int):
    """Concrete empty cache (pos arrays start at -1 = empty slot)."""

    def mk(s):
        if s.dtype == jnp.int32:
            return jnp.full(s.shape, -1, s.dtype)
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree.map(mk, model.cache_shape(batch, max_len))


def _is_ring_leaf(x, ring: int) -> bool:
    # cache leaves are layer-stacked: attention rings are (L, B, S, ...)
    # with S = the slot ring; recurrent state has no slot dimension
    return x.ndim >= 3 and x.shape[2] == ring


def kv_cache_bytes_per_token(cache, ring: int) -> float:
    """Bytes of KV state one *valid* token occupies in ``cache``.

    Counts floating-point leaves with a ``ring`` slot dimension (layer-
    stacked ``(L, batch, ring, ...)``) at leaf bytes over
    ``batch × ring`` — the int32 ``pos`` ring is slot bookkeeping, not
    handed-off model state, and recurrent-state leaves (no slot dim) are
    per-sequence, not per-token (``kv_cache_state_bytes_per_seq``).
    This is the serving-side analogue of
    ``repro.core.bubbletea.InferenceModelSpec.kv_bytes_per_token``."""
    total = 0.0
    for x in jax.tree.leaves(cache):
        if jnp.issubdtype(x.dtype, jnp.floating) and _is_ring_leaf(x, ring):
            total += x.size * x.dtype.itemsize / (x.shape[1] * ring)
    return total


def kv_cache_state_bytes_per_seq(cache, ring: int) -> float:
    """Per-sequence bytes of recurrent state in ``cache`` (ssm/rwkv
    conv + state leaves, which have no ``ring`` slot dimension).  Zero
    for pure-attention caches; moves wholesale per request on handoff."""
    total = 0.0
    for x in jax.tree.leaves(cache):
        if (jnp.issubdtype(x.dtype, jnp.floating)
                and not _is_ring_leaf(x, ring)):
            total += x.size * x.dtype.itemsize / x.shape[1]
    return total


@dataclasses.dataclass
class Request:
    req_id: int
    prompt: np.ndarray  # (T,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    # filled during processing
    generated: Optional[List[int]] = None
    ttft_ms: float = 0.0
    tbt_ms: List[float] = dataclasses.field(default_factory=list)


class ServingEngine:
    """Batched serving over one model replica."""

    def __init__(self, cfg: ModelConfig, params: Any, max_batch: int, max_len: int):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self._prefill = jax.jit(self.model.prefill)
        model = self.model  # not self: a closure over self would make a
        # reference cycle that keeps the weights on the device after the
        # engine is dropped, until the cycle collector happens to run

        def _prefill_masked(params, batch, cache):
            # ragged batches carry pad slots at position -1; the pallas
            # flash kernel ignores positions, so pin the masking (xla)
            # sdpa at trace time (the decode kernel DOES mask kv_pos<0,
            # so decode needs no pinning).
            with attention.force_impl("xla"):
                return model.prefill(params, batch, cache)

        self._prefill_masked = jax.jit(_prefill_masked)
        self._decode = jax.jit(self.model.decode_step)
        # recurrent families (mamba/rwkv/hybrid) scan every input token
        # into their state — pad slots cannot be masked by positions, so
        # ragged batches must be served per-request (see generate/serve)
        self._recurrent = cfg.rwkv is not None or cfg.family in ("ssm", "hybrid")

    def prefill_logits(self, requests: List[Request]) -> Tuple[jax.Array, Any]:
        """Right-aligned batched prefill. Returns (last-token logits, cache).

        Pad slots carry position -1 — ``sdpa``/the decode kernel treat
        negative positions as empty and mask them, so for attention
        models a short prompt's output does not depend on its batch
        neighbours; each request then decodes from its own prompt
        length.  Recurrent families cannot mask pads this way — their
        ragged batches are split upstream (``generate``/``serve``)."""
        assert len(requests) <= self.max_batch
        B = len(requests)
        T = max(len(r.prompt) for r in requests)
        toks = np.zeros((B, T), np.int32)
        pos2d = np.full((B, T), -1, np.int32)
        for i, r in enumerate(requests):
            L = len(r.prompt)
            toks[i, T - L:] = r.prompt  # right-align
            pos2d[i, T - L:] = np.arange(L)
        positions = jnp.asarray(pos2d)
        if self.cfg.mrope_sections is not None:
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        cache = zeros_cache(self.model, B, self.max_len)
        prefill = self._prefill_masked if self._ragged(requests) else self._prefill
        return prefill(
            self.params,
            {"tokens": jnp.asarray(toks), "positions": positions},
            cache,
        )

    def prefill_batch(self, requests: List[Request]) -> Tuple[Any, jax.Array, jax.Array]:
        """``prefill_logits`` plus the first sample and TTFT.
        Returns (cache, next_tokens, pos)."""
        t0 = time.perf_counter()
        logits, cache = self.prefill_logits(requests)
        logits.block_until_ready()
        wall = (time.perf_counter() - t0) * 1e3
        for r in requests:
            r.ttft_ms = wall
            r.generated = []
        nxt = self._sample(logits, requests)
        pos = jnp.asarray([len(r.prompt) for r in requests], jnp.int32)
        for i, r in enumerate(requests):
            r.generated.append(int(nxt[i]))
        return cache, nxt, pos

    def decode_batch(self, requests: List[Request], cache, tokens, pos, steps: int,
                     step0: int = 1):
        """``step0`` is the sampling-step index of the first decode step
        (the prefill sample is step 0), threaded into ``_sample`` so each
        step draws from a distinct PRNG stream."""
        for k in range(steps):
            t0 = time.perf_counter()
            logits, cache = self._decode(self.params, cache, tokens, pos)
            logits.block_until_ready()
            wall = (time.perf_counter() - t0) * 1e3
            tokens = self._sample(logits, requests, step=step0 + k)
            pos = pos + 1
            for i, r in enumerate(requests):
                if len(r.generated) < r.max_new_tokens:
                    r.generated.append(int(tokens[i]))
                    r.tbt_ms.append(wall)
        return cache, tokens, pos

    def _sample(self, logits: jax.Array, requests: List[Request],
                step: int = 0) -> jax.Array:
        temps = np.array([r.temperature for r in requests], np.float32)
        if (temps == 0).all():
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # key = hash of the req-id *tuple* (order-sensitive, unlike the
        # old sum, which collided for any two batches with equal id sums)
        # with the sampling step folded in — without the fold, every
        # decode step reused the identical key and draws were perfectly
        # correlated across steps
        seed = hash(tuple(r.req_id for r in requests)) & 0x7FFFFFFF
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        scaled = logits / jnp.maximum(jnp.asarray(temps)[:, None], 1e-3)
        return jax.random.categorical(key, scaled).astype(jnp.int32)

    def _ragged(self, requests: List[Request]) -> bool:
        T = max(len(r.prompt) for r in requests)
        return any(len(r.prompt) != T for r in requests)

    def split_ragged_recurrent(self, requests: List[Request], serve_fn) -> bool:
        """Recurrent families scan pads into their state (positions can't
        mask them): serve such ragged batches per-request via ``serve_fn``.
        Returns True when the batch was handled that way."""
        if self._recurrent and self._ragged(requests):
            for r in requests:
                serve_fn([r])
            return True
        return False

    def generate(self, requests: List[Request]) -> List[Request]:
        if self.split_ragged_recurrent(requests, self.generate):
            return requests
        cache, tok, pos = self.prefill_batch(requests)
        steps = max(r.max_new_tokens for r in requests) - 1
        self.decode_batch(requests, cache, tok, pos, steps)
        return requests


class SplitwiseCluster:
    """Prefill on one engine, decode on another (KV handoff in between)."""

    def __init__(self, cfg: ModelConfig, params: Any, max_batch: int, max_len: int):
        self.prefill_engine = ServingEngine(cfg, params, max_batch, max_len)
        self.decode_engine = ServingEngine(cfg, params, max_batch, max_len)
        self.kv_bytes_moved = 0

    def serve(self, requests: List[Request]) -> List[Request]:
        if self.prefill_engine.split_ragged_recurrent(requests, self.serve):
            return requests
        cache, tok, pos = self.prefill_engine.prefill_batch(requests)
        # KV handoff (Splitwise): device-to-device copy.  Count only the
        # *valid* slots — the ring is B × max_len and mostly empty pads,
        # so summing whole leaves over-counted by up to max_len/prompt_len
        # per request and disagreed with the latency model's
        # kv_bytes_per_token × prompt_tokens pricing.
        eng = self.prefill_engine
        # sliding-window configs allocate a shrunken slot ring
        # (attention.py: S = min(max_len, cfg.window))
        ring = min(eng.max_len, eng.cfg.window) if eng.cfg.window else eng.max_len
        per_token = kv_cache_bytes_per_token(cache, ring)
        per_seq = kv_cache_state_bytes_per_seq(cache, ring)
        self.kv_bytes_moved += per_token * sum(
            min(len(r.prompt), ring) for r in requests
        ) + per_seq * len(requests)
        cache = jax.tree.map(jnp.copy, cache)
        steps = max(r.max_new_tokens for r in requests) - 1
        self.decode_engine.decode_batch(requests, cache, tok, pos, steps)
        return requests
