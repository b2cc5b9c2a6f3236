"""Model FLOPs from a configuration's sizes (the ``configs/*.json`` keys).

Convention (PaLM, Chowdhery et al. 2022, appendix B): a matmul of an
``m x k`` by a ``k x n`` matrix is ``2mkn`` operations; training is three
times the forward pass; attention counts the whole ``T x T`` score matrix
(what the XLA path computes).  Recomputation (remat) is not counted, and
neither is the embedding gather, which is no matmul.
"""
from __future__ import annotations


def layer_matmul_params(sizes: dict) -> int:
    """Matmul weights of one dense transformer layer."""
    d, hd = sizes["d_model"], sizes["head_dim"]
    attn = 2 * d * sizes["num_heads"] * hd + 2 * d * sizes["num_kv_heads"] * hd
    glu = 3 if sizes["ffn_activation"] == "swiglu" else 2
    return attn + glu * d * sizes["d_ff"]


def head_params(sizes: dict) -> int:
    return sizes["d_model"] * sizes["vocab_size"]


def matmul_params(sizes: dict) -> int:
    """N of the 6N rule: every layer's matmul weights plus the LM head."""
    return sizes["num_layers"] * layer_matmul_params(sizes) + head_params(sizes)


def _attention_flops(sizes: dict, T: int) -> int:
    """Forward QK^T and PV over a length-``T`` sequence, all layers."""
    return 4 * sizes["num_layers"] * sizes["num_heads"] * sizes["head_dim"] * T * T


def train_flops_per_token(sizes: dict, seq_len: int) -> float:
    """6N + 12·L·H·Q·T per token (forward and backward)."""
    return 6 * matmul_params(sizes) + 3 * _attention_flops(sizes, seq_len) / seq_len


def train_step_flops(sizes: dict, batch: int, seq_len: int) -> float:
    return train_flops_per_token(sizes, seq_len) * batch * seq_len

