"""The benchmark's arithmetic: operations and bytes computed from shapes.

Kept here, not in the program, so that no later PR changes what a share of a
peak is a share OF. Every function says what it counts.
"""

from __future__ import annotations


def matmul_params(cfg) -> int:
    """Parameters that sit in a matrix multiplication on every token, of a
    dense ``TransformerConfig``: q, k, v, o, the MLP (three matrices where it
    is gated) and the output head. NOT counted: the embedding table (a
    lookup), position embeddings, norms and biases."""
    d, dh = cfg.d_model, cfg.head_dim
    attn = d * cfg.n_heads * dh * 2 + d * cfg.kv_heads * dh * 2
    mlp = d * cfg.ff_dim * (3 if cfg.activation == "swiglu" else 2)
    return cfg.n_layers * (attn + mlp) + d * cfg.vocab_size


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward operations per trained token: 6 per matmul
    parameter, plus causal attention's two batched products (QK^T, PV) at
    half the square: forward 2*2*(seq/2)*H*Dh per token per layer, times 3
    for the backward. Recomputation (remat, flash's backward recompute) is
    not counted."""
    attn = 6.0 * cfg.n_layers * seq * cfg.n_heads * cfg.head_dim
    return 6.0 * matmul_params(cfg) + attn


def weight_bytes(cfg, bytes_per_param: int = 2) -> int:
    """Bytes of the weights one decode step must read: every matmul
    parameter once (a batch of rows shares them)."""
    return matmul_params(cfg) * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_elem: int = 2) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return cfg.n_layers * 2 * cfg.kv_heads * cfg.head_dim * bytes_per_elem


def decode_bytes(cfg, live_kv_tokens: int) -> int:
    """The least a decode step reads from HBM: the weights once and the keys
    and values of every live token of the rows it advances. Activations,
    block tables and the logits it writes are not counted."""
    return weight_bytes(cfg) + kv_bytes_per_token(cfg) * int(live_kv_tokens)
