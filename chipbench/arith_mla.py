"""The benchmark's arithmetic for a latent-attention sparse stack (kanana-2 /
DeepSeek-V3 shaped: MLA mixers, leading dense layers, routed experts of which
this chip holds a share, ungated shared experts): operations and bytes
computed from shapes, beside ``arith.py`` (dense), ``arith_moe.py`` (every
expert held) and ``arith_hybrid.py`` (DeltaNet periods). Kept with the
benchmark, so that no later PR changes what a share of a peak is a share OF.
Every function says what it counts and what it does not. ``cfg`` is the
program's ``TransformerConfig`` with ``mla_*`` widths, ``lead_layers`` leading
layers of ``lead_kind`` and a ``pattern`` of ("mla", "moe") layers.
"""

from __future__ import annotations

# products of a routed layer in a training step: gate, up and down projections,
# each forward, gradient of the input, gradient of the weights
GROUPED_GEMMS_PER_LAYER = 9


def mla_layers(cfg) -> int:
    """Layers whose mixer is latent attention: here every layer."""
    period = cfg.pattern
    periods = (cfg.n_layers - cfg.lead_layers) // len(period)
    lead = cfg.lead_layers if cfg.lead_layers and cfg.lead_kind[0] == "mla" else 0
    return lead + periods * sum(1 for mixer, _ in period if mixer == "mla")


def dense_layers(cfg) -> int:
    """Layers whose FFN is the dense one (width ``dense_ff_dim``)."""
    return cfg.n_layers - cfg.routed_layers


def mla_params(cfg) -> int:
    """Matrix parameters of ONE latent-attention mixer: q [D, H (dc + dr)],
    the down-projection [D, r + dr], the up-projection [r, H (dc + dv)] and
    the output projection [H dv, D]. NOT counted: the latent's gain."""
    d, h = cfg.d_model, cfg.n_heads
    dc, dr, dv, r = (cfg.mla_qk_content_dim, cfg.mla_qk_rope_dim,
                     cfg.mla_v_dim, cfg.mla_kv_rank)
    return d * h * (dc + dr) + d * (r + dr) + r * h * (dc + dv) + h * dv * d


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for EVERY token,
    whatever the router does: every layer's MLA mixer (``mla_params``); a
    dense layer's three FFN matrices at the dense width; per routed layer the
    router (all experts wide) and the shared experts' three matrices; the
    output head over the vocabulary held here. NOT counted: the embedding (a
    lookup), gains, the selection bias, and the routed experts
    (``train_flops_per_token`` counts the rows they really compute)."""
    d = cfg.d_model
    dense = 3 * d * cfg.dense_ff_dim
    routed = d * cfg.n_experts + 3 * d * cfg.moe_shared_expert_ff
    return (mla_layers(cfg) * mla_params(cfg) + dense_layers(cfg) * dense
            + cfg.routed_layers * routed + d * cfg.vocab_size)


def mla_core_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Operations causal attention REQUIRES in a training step at scores
    ``head_dim`` (content + rope) wide and values ``mla_v_dim`` wide, 2 per
    multiply-add, over half the square (the causal half): forward Q K^T and
    P V, ``batch x heads x seq^2 x (dq + dv)``; backward at its own count, the
    four products dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q: twice the
    forward's. Over the MLA layers. NOT counted: the forward computed again
    under remat, the scores a flash kernel computes again in its backward,
    blocks above the diagonal a kernel computes and masks, padding of 192 up
    to a lane tile, the softmax."""
    per_layer = float(batch) * cfg.n_heads * seq * seq * (cfg.head_dim + cfg.mla_v_dim)
    return 3.0 * per_layer * mla_layers(cfg)


def mla_core_bytes_per_step(cfg, batch: int, seq: int, bytes_per_elem: int = 2) -> float:
    """The least the same products move through HBM: forward reads q, k
    (``head_dim``) and v and writes o (``mla_v_dim``) once; backward reads q,
    k, v, o and o's gradient and writes the gradients of q, k and v. NOT
    counted: the logsumexp rows, a K/V block read again for every query block,
    recomputation."""
    dq, dv = cfg.head_dim, cfg.mla_v_dim
    per_token_head = (2 * dq + 2 * dv) + (2 * dq + 3 * dv) + (2 * dq + dv)
    return (float(batch) * seq * cfg.n_heads * per_token_head * bytes_per_elem
            * mla_layers(cfg))


def held_gemm_flops_per_step(cfg, held_rows_per_step: float) -> float:
    """Operations the grouped GEMMs of one training step require on ONE
    RANK'S SHARE: nine products a ROUTED layer of 2 x rows x d_model x EXPERT
    width (``ff_dim``, never the dense layer's), rows = the token-choices that
    fell on the experts held here, summed over the routed layers
    (``held_rows_per_step``: the program's ``moe_held_rows``). NOT counted:
    rows padded up to a tile, tiles computed for a short group, the forward
    products computed again under remat."""
    return GROUPED_GEMMS_PER_LAYER * 2.0 * held_rows_per_step * cfg.d_model * cfg.ff_dim


def held_gemm_bytes_per_step(cfg, held_rows_per_step: float,
                             bytes_per_elem: int = 2) -> float:
    """The least the same nine products move through HBM: each reads or
    writes its two row operands once (held rows x d_model and held rows x
    expert width) and the HELD experts' matrix of that projection once in
    every ROUTED layer, at ``bytes_per_elem`` (bf16). NOT counted: a weight
    tile read again for every row tile, float32 accumulators, the transposes
    around the weight-gradient kernel, recomputation, and the dense layers
    (they run no grouped GEMM)."""
    rows = held_rows_per_step * (cfg.d_model + cfg.ff_dim)
    weights = cfg.routed_layers * cfg.experts_held * cfg.d_model * cfg.ff_dim
    return GROUPED_GEMMS_PER_LAYER * float(rows + weights) * bytes_per_elem


def train_flops_per_token(cfg, seq: int, held_rows_per_token: float) -> float:
    """Forward + backward operations per trained token: 6 per matmul parameter
    every token meets (``matmul_params_per_token``), plus 6 x the three expert
    matrices (d_model x expert width) x the expert rows this chip computed per
    token (``held_rows_per_token``: held rows summed over the routed layers,
    over the tokens: what the held share really multiplied, NOT k a layer),
    plus causal attention at half the square in the MLA layers (3 x seq x
    heads x (score width + value width) a layer and token). Recomputation
    (remat), the router's top-k, sorts, gathers, softmaxes, RoPE and pad rows
    are not counted."""
    experts = 6.0 * 3 * cfg.d_model * cfg.ff_dim * held_rows_per_token
    attn = 3.0 * mla_layers(cfg) * seq * cfg.n_heads * (cfg.head_dim + cfg.mla_v_dim)
    return 6.0 * matmul_params_per_token(cfg) + experts + attn
