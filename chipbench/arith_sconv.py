"""The benchmark's arithmetic for a short-convolution / attention hybrid sparse
stack (LFM2 shaped: mixers "sconv" and "attn" in one ``layer_pattern``, leading
dense layers, routed experts of which this chip holds a share, no shared
expert, a tied head): operations and bytes computed from shapes, beside
``arith.py`` (dense), ``arith_moe.py`` (every expert held), ``arith_hybrid.py``
(DeltaNet periods), ``arith_mla.py`` (latent attention) and ``arith_swa.py``
(window / full kinds). Kept with the benchmark, so that no later PR changes
what a share of a peak is a share OF: the counts read the WORK, whatever
implements it. Every function says what it counts and what it does not.
``cfg`` is the program's ``TransformerConfig`` with ``sconv_taps``,
``lead_layers`` leading layers of ``lead_kind`` and a ``pattern`` of
("sconv" | "attn", "moe") layers. The held experts' grouped GEMMs are
``arith_mla.held_gemm_*``'s count.
"""

from __future__ import annotations


def layers_of(cfg, mixer: str) -> int:
    """Layers whose mixer is ``mixer`` ("sconv" or "attn"), leading ones too."""
    period = cfg.pattern
    periods = (cfg.n_layers - cfg.lead_layers) // len(period)
    lead = cfg.lead_layers if cfg.lead_layers and cfg.lead_kind[0] == mixer else 0
    return lead + periods * sum(1 for m, _ in period if m == mixer)


def mix_bytes_per_step(cfg, batch: int, seq: int, bytes_per_elem: int = 2) -> float:
    """The least the pass BETWEEN a convolution mixer's two projections (gate
    before, taps, gate after) moves through HBM in a training step, over all
    "sconv" layers: forward one read of the projection's [rows, 3 D] and one
    write of [rows, D]; backward one read of [rows, 3 D] and of the
    cotangent [rows, D] and one write of d[rows, 3 D]: 11 x rows x D elements
    a layer at ``bytes_per_elem`` (bf16). NOT counted: the forward run again
    under remat (the replay is not required work), the taps and their
    gradient (K x D numbers), any intermediate a several-pass form writes and
    reads back, float32 copies."""
    rows = float(batch) * seq
    return 11.0 * rows * cfg.d_model * bytes_per_elem * layers_of(cfg, "sconv")


def mix_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Operations the same pass requires, a multiplication or an addition
    each, K = ``sconv_taps``: forward B * x (1), the taps' sum (2 K - 1), C *
    (1) an element of [rows, D]; backward the products u and c again (they are
    not kept: 2 K), C's and c's cotangents (2), the taps' transpose (2 K - 1),
    B's and x's cotangents (2), the taps' own gradient (2 K): 8 K + 5 in all.
    They run on the vector units, far under any peak: the bytes bind."""
    K = cfg.sconv_taps
    return (8.0 * K + 5.0) * batch * seq * cfg.d_model * layers_of(cfg, "sconv")


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for EVERY token,
    whatever the router does: a convolution mixer's two projections (D x 3 D
    and D x D), an attention mixer's four (q and o at the query heads, k and v
    at the KV heads), a dense layer's three FFN matrices at the dense width,
    per routed layer the router (all experts wide), the output head over the
    vocabulary held here (tied to the embedding: still a product). NOT
    counted: the embedding's lookup, gains, taps, and the routed experts
    (``train_flops_per_token`` counts the rows they really compute)."""
    d = cfg.d_model
    mixers = (layers_of(cfg, "sconv") * 4 * d * d
              + layers_of(cfg, "attn") * 2 * d * cfg.head_dim * (cfg.n_heads + cfg.kv_heads))
    dense = (cfg.n_layers - cfg.routed_layers) * 3 * d * cfg.dense_ff_dim
    return mixers + dense + cfg.routed_layers * d * cfg.n_experts + d * cfg.vocab_size


def attn_core_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Operations the causal attention cores of the "attn" layers REQUIRE in a
    training step, 2 per multiply-add, over the visible (query, key) pairs
    (seq (seq + 1) / 2 a sequence and head): forward Q K^T and P V; backward
    its own four products, twice the forward's. NOT counted: the masked half
    of a diagonal block, a flash backward's recomputed scores, the softmax."""
    pairs = seq * (seq + 1) / 2.0
    per_layer = float(batch) * cfg.n_heads * pairs * 2 * cfg.head_dim * 2
    return 3.0 * per_layer * layers_of(cfg, "attn")


def train_flops_per_token(cfg, seq: int, held_rows_per_token: float) -> float:
    """Forward + backward operations per trained token: 6 per matmul parameter
    every token meets (``matmul_params_per_token``), plus 6 x the three expert
    matrices (d_model x expert width) x the expert rows this chip computed per
    token (``held_rows_per_token``: held rows summed over the routed layers,
    over the tokens: what the held share really multiplied, NOT k a layer),
    plus the attention layers' causal cores (``attn_core_flops_per_step`` of
    one sequence, over its tokens). Recomputation (remat), the convolution's
    elementwise pass, the router's top-k, sorts, gathers, softmaxes, norms,
    RoPE and pad rows are not counted."""
    experts = 6.0 * 3 * cfg.d_model * cfg.ff_dim * held_rows_per_token
    return (6.0 * matmul_params_per_token(cfg) + experts
            + attn_core_flops_per_step(cfg, 1, seq) / seq)
