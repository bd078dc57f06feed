"""The benchmark's arithmetic for a hybrid stack (Qwen3-Next: Gated DeltaNet
and gated full-attention layers, routed experts of which this chip holds a
share, a shared expert): operations and bytes computed from shapes, beside
``arith.py`` (a dense model) and ``arith_moe.py`` (softmax attention in every
layer, every expert held). Kept with the benchmark, so that no later PR changes
what a share of a peak is a share OF. Every function says what it counts.
``cfg`` is the program's ``TransformerConfig`` with a ``pattern`` of
("gdn" | "gated_attn", "moe") layers.
"""

from __future__ import annotations

# tokens a chunk of the chunked rule that trains (ops/gated_delta.CHUNK,
# restated: the count must not move with the program)
CHUNK = 64


def _layers(cfg, mixer: str) -> int:
    period = cfg.pattern
    return cfg.n_layers // len(period) * sum(1 for m, _ in period if m == mixer)


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for EVERY token,
    whatever the router does: per DeltaNet layer the qkvz, ba and output
    projections; per attention layer q (with its gate half), k, v, o; per
    layer the router (all experts wide), the shared expert's three matrices
    and its gate; the output head over the vocabulary held here. NOT counted:
    the embedding (a lookup), gains, the convolution (elementwise), A_log,
    dt_bias, and the routed experts (``train_flops_per_token`` counts the
    rows they really compute)."""
    d = cfg.d_model
    hk, hv, dk, dv = (cfg.gdn_key_heads, cfg.gdn_value_heads,
                      cfg.gdn_key_dim, cfg.gdn_value_dim)
    gdn = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    attn = (d * cfg.n_heads * cfg.head_dim * 2 + 2 * d * cfg.kv_heads * cfg.head_dim
            + cfg.n_heads * cfg.head_dim * d)
    ffn = d * cfg.n_experts + 3 * d * cfg.moe_shared_expert_ff + d
    return (_layers(cfg, "gdn") * gdn + _layers(cfg, "gated_attn") * attn
            + cfg.n_layers * ffn + d * cfg.vocab_size)


def gdn_scan_flops_per_token(cfg) -> float:
    """Forward operations per token and DeltaNet layer that the chunked gated
    delta rule REQUIRES at its chunk size C = ``CHUNK`` (``ops/gated_delta.py``'s
    own docstring lists them), 2 per multiply-add, per value head and chunk:
    K_beta K^T and Q K^T 2 x 2 C^2 dk; the ten C^3 products of (I + A)^-1;
    W and U 2 C^2 (dk + dv); W S, (Q e^gamma) S and K^T V_new 3 x 2 C dk dv;
    scores x V_new 2 C^2 dv. Divided by C tokens, times the value heads. NOT
    counted: the decays, cumulative sums and masks (elementwise), and
    anything computed twice because of remat."""
    c, dk, dv = CHUNK, cfg.gdn_key_dim, cfg.gdn_value_dim
    per_chunk = (2 * 2 * c * c * dk + 10 * 2 * c ** 3 + 2 * c * c * (dk + dv)
                 + 3 * 2 * c * dk * dv + 2 * c * c * dv)
    return cfg.gdn_value_heads * per_chunk / c


def gdn_scan_flops_per_step(cfg, tokens: int) -> float:
    """``gdn_scan_flops_per_token`` forward plus its backward at twice that
    (every product has two transposes), over the DeltaNet layers."""
    return 3.0 * gdn_scan_flops_per_token(cfg) * tokens * _layers(cfg, "gdn")


def gdn_scan_bytes_per_step(cfg, tokens: int) -> float:
    """The least the rule moves through HBM in a training step, per DeltaNet
    layer and token: forward it reads q, k, v (compute dtype, 2 bytes, at the
    value heads' count: q and k arrive repeated), g and beta (float32) and
    writes o (float32); backward it reads all six again with o's gradient and
    writes the five inputs' gradients. NOT counted: W, U, the per-chunk
    matrices, the states kept for the backward, and any recomputation."""
    hv, dk, dv = cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    inputs = hv * (2 * dk * 2 + dv * 2 + 2 * 4)
    out = hv * dv * 4
    return float(3 * inputs + 2 * out + out) * tokens * _layers(cfg, "gdn")


# products of a routed layer in a training step: gate, up and down projections,
# each forward, gradient of the input, gradient of the weights
GROUPED_GEMMS_PER_LAYER = 9


def held_gemm_flops_per_step(cfg, held_rows_per_step: float) -> float:
    """Operations the grouped GEMMs of one training step require on ONE
    RANK'S SHARE: nine products a layer of 2 x rows x d_model x expert width,
    rows = the token-choices that fell on the experts held here, summed over
    the layers (``held_rows_per_step``: the program's ``moe_held_rows`` of the
    last traced step, NOT tokens x k, which ``arith_moe`` counts for a model that
    holds every expert). NOT counted: rows padded up to a tile, tiles computed
    for a short group, the forward products computed again under remat."""
    return GROUPED_GEMMS_PER_LAYER * 2.0 * held_rows_per_step * cfg.d_model * cfg.ff_dim


def held_gemm_bytes_per_step(cfg, held_rows_per_step: float,
                             bytes_per_elem: int = 2) -> float:
    """The least the same nine products move through HBM: each reads or
    writes its two row operands once (held rows x d_model and held rows x
    expert width) and the HELD experts' matrix of that projection once in
    every layer (read, or written for the weight gradient), at
    ``bytes_per_elem`` (bf16). At 320 rows an expert the weights are most of
    it. NOT counted: a weight tile read again for every row tile, float32
    accumulators, the transposes around the weight-gradient kernel,
    recomputation."""
    rows = held_rows_per_step * (cfg.d_model + cfg.ff_dim)
    weights = cfg.n_layers * cfg.experts_held * cfg.d_model * cfg.ff_dim
    return GROUPED_GEMMS_PER_LAYER * float(rows + weights) * bytes_per_elem


def train_flops_per_token(cfg, seq: int, held_rows_per_token: float) -> float:
    """Forward + backward operations per trained token: 6 per matmul parameter
    every token meets (``matmul_params_per_token``), plus 6 x the three expert
    matrices x the expert rows this chip computed per token
    (``held_rows_per_token``: the last traced step's held rows (untraced: the
    last step's) summed over the layers, over the tokens: what the held share
    really multiplied, NOT k a layer), plus causal attention's two batched products at half the square in
    the attention layers (6 x seq x heads x head size a layer and token), plus
    three times the chunked rule's forward operations in the DeltaNet layers.
    Recomputation (remat), the router's top-k, sorts, gathers, softmaxes and
    pad rows are not counted."""
    experts = 6.0 * 3 * cfg.d_model * cfg.ff_dim * held_rows_per_token
    attn = 6.0 * _layers(cfg, "gated_attn") * seq * cfg.n_heads * cfg.head_dim
    rule = 3.0 * gdn_scan_flops_per_token(cfg) * _layers(cfg, "gdn")
    return 6.0 * matmul_params_per_token(cfg) + experts + attn + rule
