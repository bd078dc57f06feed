"""The benchmark's arithmetic for Olmo Hybrid (Gated DeltaNet layers at their
own head widths beside unrotated full attention, a dense SwiGLU in every layer,
no expert): parameters and operations computed from shapes, beside
``arith_hybrid.py`` (whose count of the chunked rule's required operations it
uses as it is: the same rule at other widths). Kept with the benchmark, so
that no later PR changes what a share of a peak is a share OF. Every function
says what it counts. ``cfg`` is the program's ``TransformerConfig`` with a
``pattern`` of ("gdn" | "attn", "mlp") layers; ``src`` the source's
``config.json`` as a dict.
"""

from __future__ import annotations

from chipbench import arith_hybrid


def parameters(src: dict, layers: int = None, vocab: int = None) -> int:
    """Every parameter of the model as the source's keys give it, at
    ``layers`` of its ``layer_types`` (default ``num_hidden_layers``) and
    ``vocab`` rows (default ``vocab_size``): per linear-attention layer the
    q, k, v, gate and output projections, the two head-wide projections (a,
    b), the convolution's taps over q, k and v, A_log, dt_bias and the output
    norm's gain; per full-attention layer q, k, v, o and the two
    whole-projection norm gains; per layer the three FFN matrices and two
    norm gains; the embedding, the untied head and the final norm."""
    d, f = src["hidden_size"], src["intermediate_size"]
    h, kv = src["num_attention_heads"], src["num_key_value_heads"]
    dh = src.get("head_dim") or d // h
    hk, hv = src["linear_num_key_heads"], src["linear_num_value_heads"]
    dk, dv = src["linear_key_head_dim"], src["linear_value_head_dim"]
    taps = src["linear_conv_kernel_dim"]
    gdn = (2 * d * hk * dk + 3 * d * hv * dv + 2 * d * hv
           + taps * (2 * hk * dk + hv * dv) + 2 * hv + dv)
    attn = 2 * d * h * dh + 2 * d * kv * dh + h * dh + kv * dh
    block = 3 * d * f + 2 * d
    types = list(src["layer_types"])[:layers or src["num_hidden_layers"]]
    full = sum(1 for kind in types if kind == "full_attention")
    return ((len(types) - full) * (gdn + block) + full * (attn + block)
            + 2 * (vocab or src["vocab_size"]) * d + d)


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for every token: per
    DeltaNet layer the q, k, v, gate, a, b and output projections; per
    attention layer q, k, v, o; per layer the three FFN matrices; the output
    head over the vocabulary held here. NOT counted: the embedding (a
    lookup), gains, the convolution (elementwise), A_log, dt_bias."""
    d = cfg.d_model
    hk, hv, dk, dv = (cfg.gdn_key_heads, cfg.gdn_value_heads,
                      cfg.gdn_key_dim, cfg.gdn_value_dim)
    gdn = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    attn = 2 * d * cfg.n_heads * cfg.head_dim + 2 * d * cfg.kv_heads * cfg.head_dim
    ffn = 3 * d * cfg.dense_ff_dim
    return (arith_hybrid._layers(cfg, "gdn") * gdn + arith_hybrid._layers(cfg, "attn") * attn
            + cfg.n_layers * ffn + d * cfg.vocab_size)


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward operations per trained token: 6 per matmul
    parameter every token meets (``matmul_params_per_token``), plus causal
    attention's two batched products at half the square in the attention
    layers (6 x seq x heads x head size a layer and token), plus three times
    the chunked rule's REQUIRED forward operations at the cell's own key and
    value widths in the DeltaNet layers
    (``arith_hybrid.gdn_scan_flops_per_token``: lanes a kernel pads are not
    counted). Recomputation (remat), norms, softmaxes and the convolutions
    are not counted."""
    attn = 6.0 * arith_hybrid._layers(cfg, "attn") * seq * cfg.n_heads * cfg.head_dim
    rule = 3.0 * arith_hybrid.gdn_scan_flops_per_token(cfg) * arith_hybrid._layers(cfg, "gdn")
    return 6.0 * matmul_params_per_token(cfg) + attn + rule
