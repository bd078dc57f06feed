"""The benchmark's arithmetic for a window / full attention hybrid sparse stack
(Laguna shaped: mixers "swa" and "attn" in one ``layer_pattern``, each with its
own head count over the same KV heads, a leading dense layer, routed experts of
which this chip holds a share, an ungated shared expert): operations and bytes
computed from shapes, beside ``arith.py`` (dense), ``arith_moe.py`` (every
expert held), ``arith_hybrid.py`` (DeltaNet periods) and ``arith_mla.py``
(latent attention). Kept with the benchmark, so that no later PR changes what
a share of a peak is a share OF. Every function says what it counts and what
it does not. ``cfg`` is the program's ``TransformerConfig`` with
``swa_window``, ``heads_of(mixer)``, ``lead_layers`` leading layers of
``lead_kind`` and a ``pattern`` of ("swa" | "attn", "moe") layers. The held
experts' grouped GEMMs are ``arith_mla.held_gemm_*``'s count.
"""

from __future__ import annotations


def layers_of(cfg, mixer: str) -> int:
    """Layers whose mixer is ``mixer`` ("swa" or "attn"), leading ones too."""
    period = cfg.pattern
    periods = (cfg.n_layers - cfg.lead_layers) // len(period)
    lead = cfg.lead_layers if cfg.lead_layers and cfg.lead_kind[0] == mixer else 0
    return lead + periods * sum(1 for m, _ in period if m == mixer)


def visible_pairs(seq: int, window: int = 0) -> float:
    """(query, key) pairs a sequence's attention REQUIRES: every key up to the
    query's own (``window`` 0: seq (seq + 1) / 2), or the query's own and the
    ``window - 1`` before it (the first ``window`` queries see fewer)."""
    if not window or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + float(seq - window) * window


def attn_params(cfg, mixer: str) -> int:
    """Matrix parameters of ONE softmax mixer of that kind: q and o at the
    kind's head count, k and v at the KV heads."""
    return 2 * cfg.d_model * cfg.head_dim * (cfg.heads_of(mixer) + cfg.kv_heads)


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for EVERY token,
    whatever the router does: every layer's attention projections
    (``attn_params`` of its kind); a dense layer's three FFN matrices at the
    dense width; per routed layer the router (all experts wide) and the
    shared expert's three matrices; the output head over the vocabulary held
    here. NOT counted: the embedding (a lookup), gains, and the routed
    experts (``train_flops_per_token`` counts the rows they really compute)."""
    d = cfg.d_model
    dense = 3 * d * cfg.dense_ff_dim
    routed = d * cfg.n_experts + 3 * d * cfg.moe_shared_expert_ff
    mixers = sum(layers_of(cfg, m) * attn_params(cfg, m) for m in ("swa", "attn"))
    return (mixers + (cfg.n_layers - cfg.routed_layers) * dense
            + cfg.routed_layers * routed + d * cfg.vocab_size)


def core_flops_per_step(cfg, mixer: str, batch: int, seq: int) -> float:
    """Operations the attention cores of the ``mixer`` layers REQUIRE in a
    training step, 2 per multiply-add, over the visible (query, key) pairs
    only (``visible_pairs``: the causal half for "attn", the window's band for
    "swa"): forward Q K^T and P V, ``batch x heads x pairs x 2 head_dim x 2``;
    backward at its own count, the four products dV = P^T dO, dP = dO V^T,
    dQ = dS K, dK = dS^T Q: twice the forward's. NOT counted: the forward
    computed again under remat, the scores a flash kernel computes again in
    its backward, the masked part of a block the kernel visits (a block of
    512 around a window of 512 is half masked), blocks it skips, the softmax."""
    pairs = visible_pairs(seq, cfg.swa_window if mixer == "swa" else 0)
    per_layer = float(batch) * cfg.heads_of(mixer) * pairs * 2 * cfg.head_dim * 2
    return 3.0 * per_layer * layers_of(cfg, mixer)


def core_bytes_per_step(cfg, mixer: str, batch: int, seq: int,
                        bytes_per_elem: int = 2) -> float:
    """The least the same products move through HBM: forward reads q (the
    kind's heads), k and v (the KV heads, unexpanded) and writes o; backward
    reads q, o, o's gradient, k and v and writes the gradients of q, k and v:
    6 x (heads + KV heads) x head_dim elements a token. NOT counted: the
    logsumexp rows, a K/V block read again for every query block (at a window
    every key block is read by two query blocks), recomputation."""
    per_token = 6 * (cfg.heads_of(mixer) + cfg.kv_heads) * cfg.head_dim
    return float(batch) * seq * per_token * bytes_per_elem * layers_of(cfg, mixer)


def train_flops_per_token(cfg, seq: int, held_rows_per_token: float) -> float:
    """Forward + backward operations per trained token: 6 per matmul parameter
    every token meets (``matmul_params_per_token``), plus 6 x the three expert
    matrices (d_model x expert width) x the expert rows this chip computed per
    token (``held_rows_per_token``: held rows summed over the routed layers,
    over the tokens: what the held share really multiplied, NOT k a layer),
    plus the attention cores over their visible pairs (``core_flops_per_step``
    of one sequence, over its tokens: causal in the full layers, the window's
    band in the window layers). Recomputation (remat), the router's top-k,
    sorts, gathers, softmaxes, RoPE, masked halves of visited blocks and pad
    rows are not counted."""
    experts = 6.0 * 3 * cfg.d_model * cfg.ff_dim * held_rows_per_token
    attn = sum(core_flops_per_step(cfg, m, 1, seq) for m in ("swa", "attn")) / seq
    return 6.0 * matmul_params_per_token(cfg) + experts + attn
