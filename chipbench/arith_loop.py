"""The benchmark's arithmetic for a LOOPED dense stack (Ouro shaped: one kind
of block, plain multi-head attention and a gated MLP, run ``steps`` times over
the same weights, the head read at every exit): parameters, operations and
bytes computed from shapes, beside ``arith.py`` (a dense stack visited once).
Kept with the benchmark, so that no later PR changes what a share of a peak is
a share OF: the counts read the WORK, whatever implements it. Every function
says what it counts and what it does not. ``cfg`` is the program's
``TransformerConfig`` (``n_layers``, ``n_heads`` = ``kv_heads``, ``head_dim``,
``dense_ff_dim``, ``vocab_size``); ``steps`` the loop's steps (the source's
``total_ut_steps``); ``src`` the source's ``config.json`` as a dict.
"""

from __future__ import annotations


def block_parameters(src: dict) -> int:
    """One block as the source's keys give it: q, k, v, o (bias-free, plain
    multi-head: 4 x D x H Dh), the gated MLP's three matrices, FOUR norm gains
    (the sandwich)."""
    d, f = src["hidden_size"], src["intermediate_size"]
    hd = src["num_attention_heads"] * src["head_dim"]
    return 4 * d * hd + 3 * d * f + 4 * d


def parameters(src: dict, layers: int = None, vocab: int = None) -> int:
    """Every parameter of the model at ``layers`` blocks (default
    ``num_hidden_layers``) and ``vocab`` rows (default ``vocab_size``): the
    blocks ONCE however often the loop visits them, the embedding and the
    UNTIED head, the final norm, the exit gate's weight and bias."""
    d = src["hidden_size"]
    rows = vocab or src["vocab_size"]
    head = 0 if src.get("tie_word_embeddings") else rows * d
    return ((layers or src["num_hidden_layers"]) * block_parameters(src)
            + rows * d + head + d + (d + 1))


def layer_visits(cfg, steps: int) -> int:
    """Blocks a token's forward pass goes through: steps x layers."""
    return int(steps) * cfg.n_layers


def matmul_params_per_token(cfg, steps: int) -> int:
    """Parameters that sit in a matrix multiplication for every token, EACH
    TIME the token meets them: a block's four projections and three MLP
    matrices x the ``layer_visits``, the head's matrix x ``steps`` exits. NOT
    counted: the embedding's lookup, gains, the gate's matvec."""
    d = cfg.d_model
    block = 2 * d * cfg.head_dim * (cfg.n_heads + cfg.kv_heads) + 3 * d * cfg.dense_ff_dim
    return layer_visits(cfg, steps) * block + int(steps) * d * cfg.vocab_size


def core_flops_per_step(cfg, steps: int, batch: int, seq: int) -> float:
    """Operations the causal attention cores REQUIRE in a training step, 2 per
    multiply-add, over the visible (query, key) pairs only (seq (seq + 1) / 2
    a head): forward Q K^T and P V, ``batch x heads x pairs x 2 head_dim x
    2``; backward at its own count, the four products dV, dP, dQ, dK: twice
    the forward's; x the ``layer_visits``. NOT counted: the forward computed
    again under remat, the scores a flash kernel computes again in its
    backward, the masked half of a diagonal block, the softmax."""
    pairs = seq * (seq + 1) / 2.0
    per_visit = float(batch) * cfg.n_heads * pairs * 2 * cfg.head_dim * 2
    return 3.0 * per_visit * layer_visits(cfg, steps)


def core_bytes_per_step(cfg, steps: int, batch: int, seq: int,
                        bytes_per_elem: int = 2) -> float:
    """The least the same products move through HBM: forward reads q, k, v
    and writes o; backward reads q, o, o's gradient, k and v and writes the
    gradients of q, k and v: 6 x (heads + KV heads) x head_dim elements a
    token and visit. NOT counted: the logsumexp rows, a K/V block read again
    for every query block, recomputation."""
    per_token = 6 * (cfg.n_heads + cfg.kv_heads) * cfg.head_dim
    return float(batch) * seq * per_token * bytes_per_elem * layer_visits(cfg, steps)


def train_flops_per_token(cfg, seq: int, steps: int) -> float:
    """Forward + backward operations per trained token: 6 per matmul
    parameter each time the token meets it (``matmul_params_per_token``: every
    visit of every block, every exit's reading of the head), plus the causal
    cores of every visit (``core_flops_per_step`` of one sequence, over its
    tokens). Recomputation (remat's replay of the steps x layers visits), the
    norms, RoPE, the softmaxes, the gate and the exit distribution are not
    counted."""
    return (6.0 * matmul_params_per_token(cfg, steps)
            + core_flops_per_step(cfg, steps, 1, seq) / seq)
