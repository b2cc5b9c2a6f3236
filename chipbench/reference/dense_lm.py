"""Plain reference of a dense decoder-only LM and its AdamW step.

Follows the configuration file: pre-norm blocks (RMSNorm with a learned
scale), multi-head attention with rotary positions (rotate-half form,
base ``rope_theta``) and a causal mask, a two-matrix FFN with the tanh
GELU, a final RMSNorm and an untied LM head.  The loss is the mean
next-token cross-entropy over positions ``0..T-2`` (a mask may narrow
it).  Everything is float32, and every matmul runs at ``HIGHEST``
precision, since a float32 matmul on a TPU otherwise runs in bfloat16.

``precision="fp8"`` is the control: the inputs of every projection, FFN
and head matmul are rounded to float8 e4m3 with a per-tensor scale (a
straight-through rounding, so gradients flow), the step below bfloat16
that would tempt an optimisation.  Attention scores stay float32.

Parameters arrive in the stored layout of the program (layer-stacked
``layers/{ln1,ln2,attn/{wq,wk,wv,wo},ffn/{w_up,w_down}}``, ``embed``,
``final_norm``, ``lm_head``); the values come from the benchmark's own
seeded generator, never from the program.
"""
from __future__ import annotations

import json
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round_fp8(x):
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(F8).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


class DenseLM:
    def __init__(self, cfg: dict, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(precision)
        self.c = cfg
        self.precision = precision
        self._key = (precision, json.dumps(cfg, sort_keys=True))

    def __hash__(self):  # a static argument of jit: equal configs share programs
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, DenseLM) and self._key == other._key

    # ---- pieces ----------------------------------------------------------

    def _mm(self, x, w):
        if self.precision == "fp8":
            x, w = _round_fp8(x), _round_fp8(w)
        return jnp.einsum("...d,df->...f", x, w, precision=HI)

    def _norm(self, x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + self.c["norm_eps"]) * scale

    def _rope(self, x, T):
        half = x.shape[-1] // 2
        freqs = self.c["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs  # (T, half)
        cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    @staticmethod
    def _gelu(x):
        return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    def _block(self, lp, x):
        c = self.c
        B, T, _ = x.shape
        H, Hkv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
        h = self._norm(x, lp["ln1"])
        q = self._rope(self._mm(h, lp["attn"]["wq"]).reshape(B, T, H, hd), T)
        k = self._rope(self._mm(h, lp["attn"]["wk"]).reshape(B, T, Hkv, hd), T)
        v = self._mm(h, lp["attn"]["wv"]).reshape(B, T, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) / math.sqrt(hd)
        causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhts,bshd->bthd", p, v, precision=HI).reshape(B, T, H * hd)
        x = x + self._mm(a, lp["attn"]["wo"])
        h = self._norm(x, lp["ln2"])
        return x + self._mm(self._gelu(self._mm(h, lp["ffn"]["w_up"])), lp["ffn"]["w_down"])

    # ---- model -----------------------------------------------------------

    def hidden(self, params, tokens):
        """Final-normed hidden states (B, T, d), float32."""
        x = jnp.take(params["embed"].astype(jnp.float32), tokens, axis=0)
        block = jax.checkpoint(self._block)
        for l in range(self.c["num_layers"]):
            x = block(jax.tree.map(lambda a: a[l].astype(jnp.float32), params["layers"]), x)
        return self._norm(x, params["final_norm"])

    def logits(self, params, h):
        return self._mm(h, params["lm_head"].astype(jnp.float32))

    def loss(self, params, tokens, mask: Optional[jax.Array] = None):
        """Mean next-token cross-entropy; ``mask`` (B, T-1) narrows it."""
        logits = self.logits(params, self.hidden(params, tokens))[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
        if mask is None:
            mask = jnp.ones_like(nll)
        return jnp.sum(nll * mask) / jnp.sum(mask)


def build(sizes: dict, precision: str = "f32") -> DenseLM:
    """The reference model of a configuration file's sizes."""
    return DenseLM(sizes, precision)


# --------------------------------------------------------------------------
# AdamW as the configuration states it
# --------------------------------------------------------------------------


def lr_at(opt: Dict, step):
    """Linear warmup to ``peak_lr``, then cosine down to
    ``min_lr_ratio * peak_lr`` at ``total_steps``; ``step`` counts from 1."""
    step = step.astype(jnp.float32)
    warm = opt["peak_lr"] * step / max(opt["warmup_steps"], 1)
    frac = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    cos = opt["peak_lr"] * (opt["min_lr_ratio"]
                            + (1 - opt["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < opt["warmup_steps"], warm, cos)


def make_train_step(model: DenseLM, opt: Dict):
    """``step(params, mu, nu, t, tokens, mask) -> (params, mu, nu, loss,
    per-leaf norms of the clipped gradient)``: one AdamW step with
    global-norm clipping, bias correction and decoupled weight decay on
    the arrays of ``opt["decay_min_ndim"]`` or more dimensions as stored."""

    def step(params, mu, nu, t, tokens, mask):
        loss, g = jax.value_and_grad(model.loss)(params, tokens, mask)
        leaves = jax.tree.leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
        scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        lr = lr_at(opt, t)
        b1, b2 = opt["b1"], opt["b2"]
        tf = t.astype(jnp.float32)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * x * x, nu, g)

        def upd(p, m, n):
            u = (m / (1 - b1 ** tf)) / (jnp.sqrt(n / (1 - b2 ** tf)) + opt["eps"])
            if p.ndim >= opt["decay_min_ndim"]:
                u = u + opt["weight_decay"] * p
            return p - lr * u

        params = jax.tree.map(upd, params, mu, nu)
        norms = jnp.stack([jnp.sqrt(jnp.sum(x * x)) for x in jax.tree.leaves(g)])
        return params, mu, nu, loss, norms

    return jax.jit(step, donate_argnums=(0, 1, 2))


def zeros_like_tree(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

