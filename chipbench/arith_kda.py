"""The benchmark's arithmetic for a KDA / latent-attention sparse stack (Kimi
Linear shaped: mixers that run the delta rule with a decay a key channel
beside latent-attention mixers, a leading dense layer, routed experts of which
this chip holds a share, an ungated shared expert): operations and bytes
computed from shapes, beside ``arith_hybrid.py`` (the scalar rule) and
``arith_mla.py`` (latent attention in every layer). Kept with the benchmark,
so that no later PR changes what a share of a peak is a share OF. Every
function says what it counts and what it does not. ``cfg`` is the program's
``TransformerConfig`` with ``kda_*`` and ``mla_*`` widths, ``lead_layers``
leading layers of ``lead_kind`` and a ``pattern`` of ("kda" | "mla", "moe")
layers.
"""

from __future__ import annotations

from chipbench import arith_mla

# tokens a chunk of the chunked rule that trains and rows a sub-block of it
# (ops/kda.py's CHUNK and SUB, restated: the count must not move with the
# program). The cut into sub-blocks is the PUBLISHED kernels' and the XLA
# form's, whose products a test counts; the kernels that run on the chip cut
# the same sums into 8 rows (more of them as products, fewer on the vector
# unit), and the count does not follow them: a finer cut is no more work done
CHUNK = 64
SUB = 16


def layers_of(cfg, mixer: str) -> int:
    """Layers whose mixer is ``mixer``, the leading ones counted."""
    period = cfg.pattern
    periods = (cfg.n_layers - cfg.lead_layers) // len(period)
    lead = cfg.lead_layers if cfg.lead_layers and cfg.lead_kind[0] == mixer else 0
    return lead + periods * sum(1 for m, _ in period if m == mixer)


def kda_params(cfg) -> int:
    """Matrix parameters of ONE KDA mixer: the q, k and v projections, beta's,
    the decay's low-rank pair, the output gate's low-rank pair and the output
    projection. NOT counted: the taps (elementwise), A_log, dt_bias, the
    per-head gain."""
    d, h, dk, dv, r = (cfg.d_model, cfg.kda_heads, cfg.kda_key_dim,
                       cfg.kda_value_dim, cfg.kda_gate_rank)
    return (d * h * (2 * dk + dv) + d * h + d * r + r * h * dk + d * r + r * h * dv
            + h * dv * d)


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for EVERY token,
    whatever the router does: every KDA mixer (``kda_params``), every
    latent-attention mixer (``arith_mla.mla_params``); a dense layer's three
    FFN matrices at the dense width; per routed layer the router (all experts
    wide) and the shared expert's three matrices; the output head over the
    vocabulary held here. NOT counted: the embedding (a lookup), gains, the
    selection bias, and the routed experts (``train_flops_per_token`` counts
    the rows they really compute)."""
    d = cfg.d_model
    dense = 3 * d * cfg.dense_ff_dim
    routed = d * cfg.n_experts + 3 * d * cfg.moe_shared_expert_ff
    return (layers_of(cfg, "kda") * kda_params(cfg)
            + layers_of(cfg, "mla") * arith_mla.mla_params(cfg)
            + (cfg.n_layers - cfg.routed_layers) * dense
            + cfg.routed_layers * routed + d * cfg.vocab_size)


def kda_scan_flops_per_token(cfg) -> float:
    """Forward operations per token and KDA layer that the chunked rule with a
    decay a key channel REQUIRES at chunk C = ``CHUNK`` and sub-blocks of
    ``SUB`` rows (``ops/kda.py``'s own docstring lists them), 2 per
    multiply-add, per head and chunk: the sub-blocks below the diagonal of KK
    and QK as products, 2 x 2 x (C^2 - C SUB) / 2 x dk; their diagonal
    sub-blocks on and below the diagonal, C (SUB + 1) / 2 pairs of rows x dk
    channels x 6 (the exponential, the key times it, a multiply-add for each
    of the two matrices: the vector unit's, counted as operations all the
    same); the ten C^3 products of (I + KK)^-1; W and U 2 C^2 (dk + dv); W S,
    (Q e^Gamma) S and K^T V_new 3 x 2 C dk dv; QK x V_new 2 C^2 dv. Divided by
    C tokens, times the heads. NOT counted: exp(Gamma) and the other factors a
    row and channel, the cumulative sum, masks, and anything computed twice
    because of remat. The numerator is the count at the published 16-row
    cut, whatever cut the kernels that are timed take; where the bytes bind
    (``kda_scan_bytes_per_step``, as at 128 / 128) the share does not move
    with it."""
    c, s, dk, dv = CHUNK, SUB, cfg.kda_key_dim, cfg.kda_value_dim
    per_chunk = (2 * 2 * (c * c - c * s) // 2 * dk + 6 * c * (s + 1) // 2 * dk
                 + 10 * 2 * c ** 3 + 2 * c * c * (dk + dv)
                 + 3 * 2 * c * dk * dv + 2 * c * c * dv)
    return cfg.kda_heads * per_chunk / c


def kda_scan_flops_per_step(cfg, tokens: int) -> float:
    """``kda_scan_flops_per_token`` forward plus its backward at twice that,
    over the KDA layers."""
    return 3.0 * kda_scan_flops_per_token(cfg) * tokens * layers_of(cfg, "kda")


def kda_scan_bytes_per_step(cfg, tokens: int) -> float:
    """The least the rule moves through HBM in a training step, per KDA layer
    and token: forward it reads q, k, v (compute dtype, 2 bytes), g IN
    FLOAT32 AT q'S SHAPE (a log-decay a key channel: 4 dk bytes a head, twice
    q's; the scalar rule reads 4) and beta (float32) and writes o (float32);
    backward it reads the five again with o's gradient and writes their
    gradients, dg per channel in float32. NOT counted: W, U, the per-chunk
    matrices, the states kept for the backward, and any recomputation."""
    h, dk, dv = cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim
    inputs = h * (2 * dk * 2 + dv * 2 + dk * 4 + 4)
    out = h * dv * 4
    return float(3 * inputs + 2 * out) * tokens * layers_of(cfg, "kda")


def train_flops_per_token(cfg, seq: int, held_rows_per_token: float) -> float:
    """Forward + backward operations per trained token: 6 per matmul parameter
    every token meets (``matmul_params_per_token``), plus 6 x the three expert
    matrices (d_model x expert width) x the expert rows this chip computed per
    token (``held_rows_per_token``: held rows summed over the routed layers,
    over the tokens), plus causal attention at half the square in the
    latent-attention layers (3 x seq x heads x (score width + value width) a
    layer and token: the "rope" dims count as score width, nothing rotates),
    plus three times the chunked rule's forward operations in the KDA layers.
    Recomputation (remat), the router's top-k, sorts, gathers, softmaxes,
    convolutions and pad rows are not counted."""
    experts = 6.0 * 3 * cfg.d_model * cfg.ff_dim * held_rows_per_token
    attn = 3.0 * layers_of(cfg, "mla") * seq * cfg.n_heads * (cfg.head_dim + cfg.mla_v_dim)
    rule = 3.0 * kda_scan_flops_per_token(cfg) * layers_of(cfg, "kda")
    return 6.0 * matmul_params_per_token(cfg) + experts + attn + rule
