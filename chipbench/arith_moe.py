"""The benchmark's arithmetic for a sparse-expert (MoE) model: operations and
bytes computed from shapes, beside ``arith.py`` (which counts a dense model and
would count one expert a layer here). Kept with the benchmark, so that no later
PR changes what a share of a peak is a share OF. Every function says what it
counts. ``cfg`` is the program's ``TransformerConfig`` of an all-MoE stack with
gated (SwiGLU) experts and no shared expert (OLMoE).
"""

from __future__ import annotations

GROUPED_GEMMS_PER_LAYER = 9   # gate, up, down: forward, d-input, d-weight each


def active_matmul_params(cfg) -> int:
    """Parameters that sit in a matrix multiplication for ONE token: q, k, v,
    o, the router, the token's ``moe_top_k`` experts (three matrices each) and
    the output head. NOT counted: the embedding table (a lookup), the norms'
    gains, and the experts a token is not routed to."""
    d, dh = cfg.d_model, cfg.head_dim
    attn = d * cfg.n_heads * dh * 2 + d * cfg.kv_heads * dh * 2
    router = d * cfg.n_experts
    experts = cfg.moe_top_k * 3 * d * cfg.ff_dim
    return cfg.n_layers * (attn + router + experts) + d * cfg.vocab_size


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward operations per trained token: 6 per ACTIVE matmul
    parameter (``active_matmul_params``) plus causal attention's two batched
    products at half the square (forward 2*2*(seq/2)*H*Dh per token per layer,
    times 3 with the backward). Recomputation, the sort / gather / unsort of
    the dispatch, the softmaxes and pad rows are not counted."""
    attn = 6.0 * cfg.n_layers * seq * cfg.n_heads * cfg.head_dim
    return 6.0 * active_matmul_params(cfg) + attn


def grouped_gemm_flops_per_step(cfg, tokens: int) -> float:
    """Operations the grouped GEMMs of one training step require: per layer
    nine products (gate, up and down projections; forward, gradient of the
    input, gradient of the weights) of 2 * rows * d_model * expert width
    each, rows = tokens * moe_top_k (every token reaches all its experts).
    NOT counted: rows padded up to a tile, tiles computed for an empty or
    partial group, recomputation."""
    rows = tokens * cfg.moe_top_k
    return (cfg.n_layers * GROUPED_GEMMS_PER_LAYER
            * 2.0 * rows * cfg.d_model * cfg.ff_dim)


def grouped_gemm_bytes_per_step(cfg, tokens: int, bytes_per_elem: int = 2) -> float:
    """The least the same nine products move through HBM: each reads or
    writes its two row operands once (rows x d_model and rows x expert width)
    and all experts' matrix of that projection once (read, or written for the
    weight gradient), at ``bytes_per_elem`` (bf16). NOT counted: a weight tile
    read again for every row tile, float32 accumulators, the transposes around
    the weight-gradient kernel."""
    rows = tokens * cfg.moe_top_k
    one = rows * (cfg.d_model + cfg.ff_dim) + cfg.n_experts * cfg.d_model * cfg.ff_dim
    return cfg.n_layers * GROUPED_GEMMS_PER_LAYER * float(one) * bytes_per_elem
