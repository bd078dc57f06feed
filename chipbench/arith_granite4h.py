"""The benchmark's arithmetic for a DENSE state-space / attention hybrid
(Granite-4.0-H shaped: mixers "ssm" and "attn" in one ``layer_pattern``, a
gated MLP in every block, a tied head, no expert): parameters, operations and
bytes computed from shapes, beside ``arith_ssm.py`` (whose counts of the
scan's required bytes and operations it uses as they are: the same scan at
other groups). Kept with the benchmark, so that no later PR changes what a
share of a peak is a share OF: the counts read the WORK, whatever implements
it. Every function says what it counts. ``cfg`` is the program's
``TransformerConfig`` with its ``ssm_*`` sizes and a ``pattern`` of ("ssm" |
"attn", "mlp") blocks; ``src`` the source's ``config.json`` as a dict.
"""

from __future__ import annotations

from chipbench import arith_ssm

# reads and writes of [rows, inner] the gated norm REQUIRES a layer and step:
# forward three reads (o, x, z) and one write, backward four reads (those and
# the cotangent) and two writes (d o, d z). The forward run again under remat
# (four more: ops/ssm_gate_norm.py's own count of 14 has them) is NOT required
# work, as in ``arith_ssm.scan_bytes_per_step``
GATE_NORM_PASSES = 10


def parameters(src: dict, layers: int = None, vocab: int = None) -> int:
    """Every parameter of the model as the source's keys give it, at the
    first ``layers`` of its ``layer_types`` (default ``num_hidden_layers``)
    and ``vocab`` rows (default ``vocab_size``): per Mamba-2 layer the two
    projections, the taps and their bias, A_log, dt_bias, D and the gated
    norm's gain; per attention layer q, k, v, o; per layer the gated MLP's
    two matrices and two norm gains; the TIED embedding once and the final
    norm."""
    d, f = src["hidden_size"], src["shared_intermediate_size"]
    h, kv = src["num_attention_heads"], src["num_key_value_heads"]
    dh = src.get("head_dim") or d // h
    H, P = src["mamba_n_heads"], src["mamba_d_head"]
    G, N, K = src["mamba_n_groups"], src["mamba_d_state"], src["mamba_d_conv"]
    inner, conv = H * P, H * P + 2 * G * N
    mamba = d * (inner + conv + H) + (K + 1) * conv + 3 * H + inner + inner * d
    attn = 2 * d * h * dh + 2 * d * kv * dh
    block = 3 * d * f + 2 * d
    types = list(src["layer_types"])[:layers or src["num_hidden_layers"]]
    full = sum(1 for kind in types if kind == "attention")
    head = 0 if src.get("tie_word_embeddings", True) else (vocab or src["vocab_size"]) * d
    return ((len(types) - full) * (mamba + block) + full * (attn + block)
            + (vocab or src["vocab_size"]) * d + head + d)


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for every token: a
    state-space mixer's two projections (D x (2 inner + 2 G N + H) and inner x
    D), an attention mixer's four, the gated MLP's three matrices in EVERY
    layer, the head's read of the (tied) embedding over the vocabulary held
    here. NOT counted: the embedding's lookup, gains, taps, biases, A, D."""
    d = cfg.d_model
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    ssm_in = 2 * inner + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads
    return (arith_ssm.layers_of(cfg, "ssm") * d * (ssm_in + inner)
            + arith_ssm.layers_of(cfg, "attn") * 2 * d * cfg.head_dim * (cfg.n_heads + cfg.kv_heads)
            + cfg.n_layers * 3 * d * cfg.dense_ff_dim + d * cfg.vocab_size)


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward operations per trained token: 6 per matmul
    parameter every token meets (``matmul_params_per_token``), plus the
    attention layers' causal cores (``arith_ssm.attn_core_flops_per_step`` of
    one sequence, over its tokens), plus 3 x the scans' REQUIRED forward
    operations (``arith_ssm.scan_forward_flops_per_token`` a state-space
    layer: ONE group's C B^T for all its heads). Recomputation (remat), the
    convolution, the gates, the gated norm's elementwise passes, the
    multipliers and the softmaxes are not counted."""
    scans = 3.0 * arith_ssm.scan_forward_flops_per_token(cfg) * arith_ssm.layers_of(cfg, "ssm")
    return (6.0 * matmul_params_per_token(cfg)
            + arith_ssm.attn_core_flops_per_step(cfg, 1, seq) / seq + scans)


def gate_norm_bytes_per_step(cfg, batch: int, seq: int, bytes_per_elem: int = 2) -> float:
    """The least the state-space mixers' epilogues (skip, gate, gated norm)
    move through HBM in a training step: ``GATE_NORM_PASSES`` reads and
    writes of [rows, inner] a layer at ``bytes_per_elem`` (bf16). NOT counted:
    the forward run again under remat, float32 copies, a statistic
    written out and read back, the gain's and the skip's partial sums, x's
    cotangent ``D d u`` (an add XLA fuses into the scan's)."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    return (float(GATE_NORM_PASSES) * batch * seq * inner * bytes_per_elem
            * arith_ssm.layers_of(cfg, "ssm"))
