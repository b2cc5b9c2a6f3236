"""The main-path Pallas kernels compile for a TPU v5e at GPT-A widths.

Each test compiles ahead of time against a described ``v5e:2x2``
topology (no chip needed) with ``interpret=False`` and checks that the
program holds the Mosaic kernel (``tpu_custom_call``).  The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rms
from repro.kernels import wkv6

# GPT-A (configs/gpt_a.py): 32 heads of 128, d_model 4096, 4096 tokens
HEADS, HEAD_DIM, D_MODEL, SEQ = 32, 128, 4096, 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles(one_chip):
    bh = (HEADS, SEQ, HEAD_DIM)
    hlo = _compile(
        lambda q, k, v: fa.flash_attention_bhsd(
            q, k, v, group=1, scale=HEAD_DIM**-0.5, causal=True, interpret=False),
        [(bh, jnp.bfloat16)] * 3, one_chip)
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles(one_chip):
    hlo = _compile(
        lambda x, s: rms.rmsnorm_rows(x, s, interpret=False),
        [((SEQ, D_MODEL), jnp.bfloat16), ((D_MODEL,), jnp.float32)], one_chip)
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles(one_chip):
    batch = 4
    hlo = _compile(
        lambda q, k, v, qp, kp: dec.decode_attention_bhsd(
            q, k, v, qp, kp, group=1, scale=HEAD_DIM**-0.5, interpret=False),
        [((batch * HEADS, 1, HEAD_DIM), jnp.bfloat16),
         ((batch * HEADS, SEQ, HEAD_DIM), jnp.bfloat16),
         ((batch * HEADS, SEQ, HEAD_DIM), jnp.bfloat16),
         ((batch * HEADS, 1), jnp.int32),
         ((batch * HEADS, SEQ), jnp.int32)], one_chip)
    assert "tpu_custom_call" in hlo


def test_wkv6_compiles(one_chip):
    # rwkv6-7b heads: 64 of 64 (configs/rwkv6_7b.py)
    bh = (64, SEQ, 64)
    hlo = _compile(
        lambda r, k, v, w, u: wkv6.wkv6_bhtd(r, k, v, w, u, interpret=False),
        [(bh, jnp.bfloat16)] * 3 + [(bh, jnp.float32), ((64, 64), jnp.float32)],
        one_chip)
    assert "tpu_custom_call" in hlo
