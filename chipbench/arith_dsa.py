"""The benchmark's arithmetic for a sparse-expert stack whose attention is a
LEARNED sparse one (Keye-VL-2.0 shaped: mixer "dsa", an indexer of
``dsa_index_heads`` heads of ``dsa_index_dim`` over one key head, ``dsa_topk``
keys a query, routed experts of which this chip holds a share): operations and
bytes computed from shapes, beside ``arith.py`` (dense), ``arith_moe.py`` and
``arith_swa.py``. Kept with the benchmark, so that no later PR changes what a
share of a peak is a share OF. The counts are the MODEL's: the core over the
pairs the queries SELECTED and the indexer over every causal pair, whatever a
program executes to get there (a core that runs every causal block under a
mask executes 4.3 times the selected pairs at 16,384 / 2,048 and is counted
the same). ``cfg`` is the program's ``TransformerConfig``.
"""

from __future__ import annotations


def causal_pairs(seq: int) -> float:
    """(query, key) pairs with key <= query."""
    return seq * (seq + 1) / 2.0


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a sequence's queries keep: query t its min(t + 1,
    topk) best keys."""
    k = min(seq, topk)
    return k * (k + 1) // 2 + (seq - k) * k


def attn_params(cfg) -> int:
    """Matrix parameters of one layer's attention: q and o at the query heads,
    k and v at the KV heads."""
    return 2 * cfg.d_model * cfg.head_dim * (cfg.n_heads + cfg.kv_heads)


def indexer_params(cfg) -> int:
    """Matrix parameters of one layer's indexer: its heads' queries, its one
    key, a weight a head."""
    return cfg.d_model * (cfg.dsa_index_heads * cfg.dsa_index_dim + cfg.dsa_index_dim
                          + cfg.dsa_index_heads)


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for EVERY token: every
    layer's attention and indexer projections and its router (all experts
    wide), the output head over the vocabulary held here. NOT counted: the
    embedding (a lookup), gains, the routed experts (``train_flops_per_token``
    counts the rows they really compute)."""
    d = cfg.d_model
    return (cfg.n_layers * (attn_params(cfg) + indexer_params(cfg) + d * cfg.n_experts)
            + d * cfg.vocab_size)


def core_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Operations the cores REQUIRE in a training step, 2 per multiply-add,
    over the SELECTED (query, key) pairs only: forward Q K^T and P V,
    ``batch x heads x pairs x 2 head_dim x 2``; backward at its own count (dV,
    dP, dQ, dK: twice the forward's). NOT counted: unselected pairs a masked
    kernel computes, the forward computed again under remat, the scores a
    flash backward computes again, the head-averaged probabilities the
    indexer's loss reads (``kl_flops_per_step``), the softmax."""
    pairs = selected_pairs(seq, cfg.dsa_topk)
    return 3.0 * float(batch) * cfg.n_heads * pairs * 2 * cfg.head_dim * 2 * cfg.n_layers


def core_bytes_per_step(cfg, batch: int, seq: int, bytes_per_elem: int = 2) -> float:
    """The least the same products move through HBM: forward reads q, k, v
    (KV heads unexpanded) and writes o; backward reads q, o, o's gradient, k
    and v and writes the gradients of q, k and v: 6 x (heads + KV heads) x
    head_dim elements a token. NOT counted: the mask (1 byte a causal pair a
    pass as this program stores it: the model has no mask, a set of 2,048
    indices a query), logsumexp rows, re-read K/V blocks, recomputation."""
    per_token = 6 * (cfg.n_heads + cfg.kv_heads) * cfg.head_dim
    return float(batch) * seq * per_token * bytes_per_elem * cfg.n_layers


def index_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Operations the indexers' SCORES require in a training step, over every
    causal pair: forward ``pairs x heads x dim`` multiply-adds (the heads'
    weighted sum is another ``pairs x heads``, not counted), once; backward at
    its own count, d qI and d kI of the loss's pass, twice the forward's. NOT
    counted: the projections (``matmul_params_per_token`` has them), the
    second reading of the scores that the loss makes, the selection (compares,
    no arithmetic of the model), remat."""
    per_layer = float(batch) * causal_pairs(seq) * cfg.dsa_index_heads * cfg.dsa_index_dim * 2
    return 3.0 * per_layer * cfg.n_layers


def index_bytes_per_step(cfg, batch: int, seq: int, bytes_per_elem: int = 2) -> float:
    """The least the scores move: forward reads qI, kI and w; backward reads
    them again and writes their gradients: 3 x (heads x dim + dim + heads)
    elements a token. NOT counted: the scores themselves (float32 [T, T] a
    layer if written out: the model never needs them whole), the mask."""
    per_token = 3 * (cfg.dsa_index_heads * cfg.dsa_index_dim + cfg.dsa_index_dim
                     + cfg.dsa_index_heads)
    return float(batch) * seq * per_token * bytes_per_elem * cfg.n_layers


def train_flops_per_token(cfg, seq: int, held_rows_per_token: float) -> float:
    """Forward + backward operations per trained token: 6 per matmul parameter
    every token meets (``matmul_params_per_token``), plus 6 x the three expert
    matrices x the expert rows this chip computed per token
    (``held_rows_per_token``: held rows summed over the routed layers, over
    the tokens), plus the indexers' scores over the causal pairs and the cores
    over the SELECTED pairs (one sequence's, over its tokens). Recomputation
    (remat), the selection, the loss's second reading of the scores and its
    head-averaged probabilities, masked work, the router's top-k, sorts,
    gathers, softmaxes, RoPE and pad rows are not counted."""
    experts = 6.0 * 3 * cfg.d_model * cfg.ff_dim * held_rows_per_token
    sparse = (core_flops_per_step(cfg, 1, seq) + index_flops_per_step(cfg, 1, seq)) / seq
    return 6.0 * matmul_params_per_token(cfg) + experts + sparse


def layer_parameters(src: dict, experts: int) -> int:
    """Parameters of one layer of the source's config ``src`` holding
    ``experts`` experts: attention, the q/k gains, the indexer (with its key
    norm's gain and bias), the router, the experts, two norm gains."""
    d, dh = src["hidden_size"], src["head_dim"]
    sa = src["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    attention = 2 * d * dh * (src["num_attention_heads"] + src["num_key_value_heads"])
    indexer = d * (hi * di + di + hi) + 2 * di
    return (attention + 2 * dh + indexer + d * src["num_experts"]
            + experts * 3 * d * src["moe_intermediate_size"] + 2 * d)


def parameters(src: dict, experts=None, layers=None, vocab=None) -> int:
    """Parameters of the model ``src`` describes (``experts`` held a layer,
    ``layers`` and ``vocab`` rows: the file's own without them): the layers,
    the embedding, the untied head and the final norm."""
    experts = experts or src.get("num_experts_held") or src["num_experts"]
    layers = layers or src["num_hidden_layers"]
    vocab = vocab or src["vocab_size"]
    d = src["hidden_size"]
    return layers * layer_parameters(src, experts) + 2 * vocab * d + d
