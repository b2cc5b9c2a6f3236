"""The plain reference against the program's ``model.loss`` at a small
size on the CPU, on the benchmark's own seeded weights.  With the program computing in float32 the two agree to
float32 rounding; in bfloat16, as configured, to bfloat16 rounding."""
import dataclasses

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from chipbench import traffic
from chipbench.reference import dense_lm
from chipbench.weights import Weights, _num_blocks, block_norms

SIZES = {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 4, "head_dim": 32,
         "d_ff": 512, "vocab_size": 512, "ffn_activation": "gelu", "rope_theta": 10000.0,
         "norm_eps": 1e-6}


def program(dtype):
    from repro.configs import load_config
    from repro.models.transformer import build_model

    cfg = dataclasses.replace(
        load_config("gpt-a"), **{k: v for k, v in SIZES.items()
                                 if k not in ("norm_eps", "rope_theta")},
        dtype=dtype, remat="none")
    return build_model(cfg)


@pytest.fixture(scope="module")
def setup():
    model = program(jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = Weights(shapes).make(2**40 + 3)
    tokens = traffic.train_batch({"batch": 2, "seq_len": 48, "zipf_a": 1.2, "structure": 0.7},
                                 SIZES["vocab_size"], 11, 0)["tokens"]
    return shapes, params, jnp.asarray(tokens)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)])
def test_loss_and_grads_match_program(setup, dtype, rtol):
    _, params, tokens = setup
    ref = dense_lm.DenseLM(SIZES)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(ref.loss)(params, tokens)
        got, g_got = jax.value_and_grad(lambda p: program(dtype).loss(p, {"tokens": tokens})[0])(params)
    assert float(got) == pytest.approx(float(want), rel=rtol)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        na, nb = float(jnp.linalg.norm(a)), float(jnp.linalg.norm(b))
        assert na == pytest.approx(nb, rel=10 * rtol)


def test_adamw_step_moves_every_matrix_by_about_lr(setup):
    shapes, params, tokens = setup
    opt = {"peak_lr": 1e-3, "min_lr_ratio": 0.1, "warmup_steps": 1, "total_steps": 10,
           "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.0, "clip_norm": 1.0,
           "decay_min_ndim": 2}
    ref = dense_lm.DenseLM(SIZES)
    step = dense_lm.make_train_step(ref, opt)
    p = jax.tree.map(jnp.copy, params)
    z = dense_lm.zeros_like_tree(p)
    mask = jnp.ones((2, 47), jnp.float32)
    p1, _, _, _, gn = step(p, z, dense_lm.zeros_like_tree(p), jnp.int32(1), tokens, mask)
    assert float(jnp.sqrt(jnp.sum(gn ** 2))) == pytest.approx(1.0, rel=1e-4)  # clipped
    w = p1["layers"]["ffn"]["w_up"] - params["layers"]["ffn"]["w_up"]
    assert float(jnp.max(jnp.abs(w))) == pytest.approx(1e-3, rel=1e-3)  # sign(g) * lr



def test_block_norms_are_row_major_blocks_of_each_leaf():
    rows = np.arange(2048 * 2048, dtype=np.float64).reshape(2048, 2048) / 2**20
    tree = {"a": jnp.asarray(rows, jnp.float32), "b": jnp.ones((3,), jnp.bfloat16)}
    got = np.asarray(block_norms(tree))
    # 2^22 elements: 4 blocks of 512 whole rows (2^20 each); 3 elements: one block
    want = np.concatenate([np.linalg.norm(rows.reshape(4, -1), axis=1), [np.sqrt(3.0)]])
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_blocks_at_gpt_a_widths():
    d, ff, V, L = 4096, 16384, 50304, 2
    assert [_num_blocks(n) for n in (V * d, L * d * d, L * d * ff, L * d, d)] == [64, 32, 64, 1, 1]
