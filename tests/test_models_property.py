"""Property-based tests (hypothesis) on model-layer invariants."""
import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.models import attention as attn
from repro.models.modules import cross_entropy_loss
from repro.models.transformer import LOSS_CHUNK, _head_weight, _lm_loss_chunked
from repro.configs import get_smoke_config

SETTINGS = dict(max_examples=20, deadline=None,
                suppress_health_check=[hypothesis.HealthCheck.too_slow])


@given(
    seed=st.integers(0, 2**31 - 1),
    T=st.integers(2, 33),
    D=st.sampled_from([16, 32, 64]),
    theta=st.sampled_from([1e4, 1e6]),
)
@settings(**SETTINGS)
def test_rope_preserves_norm_and_relative_positions(seed, T, D, theta):
    """RoPE is a rotation: preserves per-head norms, and q·k depends only
    on relative position."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (1, T, 2, D))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (1, T))
    r = attn.apply_rope(x, pos, theta)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(r), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5, atol=1e-5,
    )
    # relative-position property: shifting both positions by c leaves
    # inner products unchanged
    q = jax.random.normal(k2, (1, T, 2, D))
    c = 7
    r0 = attn.apply_rope(q, pos, theta)
    k0 = attn.apply_rope(x, pos, theta)
    r1 = attn.apply_rope(q, pos + c, theta)
    k1_ = attn.apply_rope(x, pos + c, theta)
    ip0 = np.einsum("bthd,bshd->bhts", np.asarray(r0), np.asarray(k0))
    ip1 = np.einsum("bthd,bshd->bhts", np.asarray(r1), np.asarray(k1_))
    np.testing.assert_allclose(ip0, ip1, rtol=2e-4, atol=2e-4)


@given(
    seed=st.integers(0, 2**31 - 1),
    B=st.integers(1, 3),
    T=st.integers(1, 2 * LOSS_CHUNK + 7),
    V=st.sampled_from([11, 64, 257]),
)
@settings(**SETTINGS)
def test_chunked_ce_equals_direct(seed, B, T, V):
    """The memory-bounded chunked CE must equal the direct computation."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    d = 8
    x = jax.random.normal(ks[0], (B, T, d))
    w = jax.random.normal(ks[1], (d, V))
    labels = jax.random.randint(ks[2], (B, T), 0, V)
    mask = (jax.random.uniform(jax.random.PRNGKey(seed + 1), (B, T)) > 0.3).astype(
        jnp.float32
    )
    if float(mask.sum()) == 0:
        mask = mask.at[0, 0].set(1.0)

    class Cfg:  # minimal cfg stand-in
        pass

    got = _lm_loss_chunked(Cfg(), x, w, labels, mask)
    logits = x @ w
    want = cross_entropy_loss(logits, labels, mask)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4, atol=1e-5)


def _ce_inputs(seed, B, T, V, d=8, dtype=jnp.float32):
    """x, head, labels and a random mask with at least one counted token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (B, T, d)).astype(dtype)
    w = jax.random.normal(ks[1], (d, V))
    labels = jax.random.randint(ks[2], (B, T), 0, V)
    mask = (jax.random.uniform(ks[3], (B, T)) > 0.3).astype(jnp.float32).at[0, 0].set(1.0)
    return x, w, labels, mask


def _assert_grads_close(got, want, rtol, atol):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


@given(
    seed=st.integers(0, 2**31 - 1),
    B=st.integers(1, 3),
    T=st.integers(1, 2 * LOSS_CHUNK + 7),
    V=st.sampled_from([11, 64, 257]),
)
@settings(**SETTINGS)
def test_chunked_ce_grads_equal_direct(seed, B, T, V):
    """The chunked CE's hand-written backward must give the gradients that
    autodiff gives for the direct computation, padded positions included."""
    args = _ce_inputs(seed, B, T, V)
    got = jax.jit(jax.grad(lambda *a: _lm_loss_chunked(None, *a), (0, 1)))(*args)
    want = jax.jit(jax.grad(lambda x, w, *a: cross_entropy_loss(x @ w, *a), (0, 1)))(*args)
    _assert_grads_close(got, want, rtol=1e-4, atol=1e-6)


def _autodiff_chunked_ce(x, w_head, labels, mask):
    """The chunked CE as plain autodiff sees it: the head's cotangent is summed
    in the scan's carry once per chunk."""
    B, T, d = x.shape
    V = w_head.shape[-1]
    chunk = min(LOSS_CHUNK, T)
    pad = (-T) % chunk
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    labels = jnp.pad(labels, ((0, 0), (0, pad)))
    mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk
    xc = x.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, nc, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        xi, li, mi = inp
        logits = jnp.einsum("btd,dv->btv", xi, w_head.astype(xi.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        onehot = jnp.arange(V, dtype=li.dtype)[None, None, :] == li[..., None]
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        return (carry[0] + jnp.sum((logz - gold) * mi), carry[1] + jnp.sum(mi)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def test_chunked_ce_grads_bf16_match_plain_autodiff():
    """With bf16 activations and an f32 head the gradients keep their dtypes
    and agree with plain autodiff of the same chunked loss to bf16 rounding."""
    x, w, labels, mask = _ce_inputs(7, 2, 2 * LOSS_CHUNK + 7, 257, d=16, dtype=jnp.bfloat16)
    loss = lambda f: lambda x, w: f(x, w, labels, mask)
    got = jax.grad(loss(lambda *a: _lm_loss_chunked(None, *a)), (0, 1))(x, w)
    want = jax.grad(loss(_autodiff_chunked_ce), (0, 1))(x, w)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == jnp.float32
    _assert_grads_close(got, want, rtol=2e-2, atol=2e-4)


def test_chunked_ce_grad_reaches_tied_embedding():
    """A tied head is the embedding transposed; its gradient must arrive in
    the embedding's (V, d) layout."""
    class Cfg:
        tie_embeddings = True

    x, w, labels, mask = _ce_inputs(11, 2, LOSS_CHUNK + 3, 64)
    params = {"embed": w.T}
    got = jax.grad(lambda p: _lm_loss_chunked(Cfg, x, _head_weight(p, Cfg), labels, mask))(params)
    want = jax.grad(lambda p: cross_entropy_loss(x @ p["embed"].T, labels, mask))(params)
    _assert_grads_close([got["embed"]], [want["embed"]], rtol=1e-4, atol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_chunked_ce_head_grad_is_one_matmul_over_all_tokens():
    """No loop carries a head-shaped array (a per-chunk accumulation of the
    head gradient), and exactly one matmul produces the (d, V) gradient."""
    B, T, d, V = 2, 3 * LOSS_CHUNK, 8, 64
    x, w, labels, mask = _ce_inputs(3, B, T, V, d=d, dtype=jnp.bfloat16)
    grad = jax.grad(lambda x, w: _lm_loss_chunked(None, x, w, labels, mask), (0, 1))
    eqns = list(_eqns(jax.make_jaxpr(grad)(x, w).jaxpr))
    loops = [e for e in eqns if e.primitive.name in ("scan", "while")]
    assert loops
    for e in loops:
        carried = [v.aval.shape for v in e.invars if hasattr(v.aval, "shape")]
        if e.primitive.name == "scan":
            n = e.params["num_consts"]
            carried = carried[n: n + e.params["num_carry"]]
        assert (d, V) not in carried, (e.primitive.name, carried)
    head_dots = [e for e in eqns if e.primitive.name == "dot_general"
                 and e.outvars[0].aval.shape == (d, V)]
    assert len(head_dots) == 1
    assert head_dots[0].outvars[0].aval.dtype == jnp.float32


@given(
    seed=st.integers(0, 2**31 - 1),
    window=st.sampled_from([4, 8, 16]),
)
@settings(**SETTINGS)
def test_sliding_window_equals_truncated_context(seed, window):
    """Windowed attention at position t must equal full attention over
    the last `window` tokens only."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, T, H, D = 1, 24, 2, 16
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    out_w = attn.sdpa(q, k, v, pos, pos, causal=True, window=window)
    t = T - 1
    lo = t - window + 1
    out_full = attn.sdpa(
        q[:, t:], k[:, lo : t + 1], v[:, lo : t + 1],
        pos[:, t:], pos[:, lo : t + 1], causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out_w[:, t]), np.asarray(out_full[:, 0]), rtol=1e-4, atol=1e-4
    )


@given(seed=st.integers(0, 2**31 - 1), chunk=st.sampled_from([8, 16, 32]))
@settings(**SETTINGS)
def test_mamba_chunked_invariant_to_chunk_size(seed, chunk):
    """SSD output must not depend on the chunk size (associativity)."""
    import dataclasses

    from repro.models import ssm as ssm_lib

    cfg0 = get_smoke_config("zamba2_2p7b")
    cfg = dataclasses.replace(cfg0, ssm=dataclasses.replace(cfg0.ssm, chunk=chunk))
    cfg_ref = dataclasses.replace(cfg0, ssm=dataclasses.replace(cfg0.ssm, chunk=64))
    p = ssm_lib.mamba2_init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 64, cfg.d_model)) * 0.1
    y1, s1 = ssm_lib.mamba2_apply(p, cfg, x.astype(cfg.dtype))
    y2, s2 = ssm_lib.mamba2_apply(p, cfg_ref, x.astype(cfg.dtype))
    np.testing.assert_allclose(
        np.asarray(y1, np.float32), np.asarray(y2, np.float32), atol=3e-2, rtol=3e-2
    )
    np.testing.assert_allclose(
        np.asarray(s1["ssm"]), np.asarray(s2["ssm"]), atol=1e-3, rtol=1e-3
    )


@given(seed=st.integers(0, 2**31 - 1))
@settings(**SETTINGS)
def test_moe_output_finite_and_capacity_bounded(seed):
    from repro.models import moe as moe_lib

    cfg = get_smoke_config("qwen2_moe_a2p7b")
    p = moe_lib.moe_init(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 32, cfg.d_model), jnp.bfloat16)
    y, aux = moe_lib.moe_apply(p, cfg, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert float(aux) >= 0.0
