"""The benchmark's arithmetic for a state-space / attention hybrid sparse stack
(Nemotron-H shaped: mixers "ssm" and "attn" in one ``layer_pattern``, blocks
whose ffn is "moe" or "none", UNGATED routed experts of which this chip holds
a share, an ungated shared expert of its own width, an untied head):
operations and bytes computed from shapes, beside ``arith.py`` (dense),
``arith_moe.py``, ``arith_hybrid.py``, ``arith_mla.py``, ``arith_swa.py`` and
``arith_sconv.py``. Kept with the benchmark, so that no later PR changes what
a share of a peak is a share OF: the counts read the WORK, whatever implements
it. Every function says what it counts and what it does not. ``cfg`` is the
program's ``TransformerConfig`` with its ``ssm_*`` sizes and a ``pattern`` of
("ssm" | "attn", "moe" | "none") blocks.
"""

from __future__ import annotations

# an ungated expert is two matrices: forward two products, backward four
GROUPED_GEMMS_PER_LAYER = 6
# tokens a chunk of the chunked scan the operations are counted at (the
# source's ``chunk_size``): the yardstick's own, whatever chunk the program runs
SCAN_CHUNK = 128


def layers_of(cfg, mixer: str) -> int:
    """Blocks whose mixer is ``mixer`` ("ssm" or "attn"), leading ones too."""
    period = cfg.pattern
    periods = (cfg.n_layers - cfg.lead_layers) // len(period)
    lead = cfg.lead_layers if cfg.lead_layers and cfg.lead_kind[0] == mixer else 0
    return lead + periods * sum(1 for m, _ in period if m == mixer)


def scan_elements_per_token(cfg) -> int:
    """Elements the scan of ONE state-space layer must move a token in a
    training step: forward one read of x [inner], B and C [groups x state
    each] and the step [heads] and one write of o [inner]; backward one read
    of those and of o's cotangent and one write of the cotangents of x, B, C
    and the step."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    operands = inner + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads
    return (operands + inner) + (operands + inner) + operands


def scan_bytes_per_step(cfg, batch: int, seq: int, bytes_per_elem: int = 2) -> float:
    """The least the scans of all "ssm" layers move through HBM in a training
    step (``scan_elements_per_token`` at ``bytes_per_elem``: bf16). NOT
    counted: the forward run again under remat (the replay is not required
    work), float32 copies, the chunks' [Q, Q] matrices and states a chunked
    form writes and reads back, A and D (heads numbers)."""
    return (float(batch) * seq * scan_elements_per_token(cfg) * bytes_per_elem
            * layers_of(cfg, "ssm"))


def scan_forward_flops_per_token(cfg, chunk: int = SCAN_CHUNK) -> float:
    """Operations one state-space layer's scan requires a token FORWARD in the
    chunked (matmul) form at a chunk of Q = ``chunk``, 2 per multiply-add: a
    head's (L o C B^T) (dt x) 2 Q P and its state's write and read 4 N P, a
    group's C B^T 2 Q N. The masked half of a chunk's square IS counted (the
    matmul form computes it); the decays' exponentials, the skip and the
    recurrence over the chunks' states are not."""
    Q, P, N = chunk, cfg.ssm_head_dim, cfg.ssm_state
    return cfg.ssm_heads * (2.0 * Q * P + 4.0 * N * P) + cfg.ssm_groups * 2.0 * Q * N


def scan_flops_per_step(cfg, batch: int, seq: int, chunk: int = SCAN_CHUNK) -> float:
    """Forward x 3 (the backward's products are twice the forward's) over all
    "ssm" layers and tokens. Recomputation under remat is NOT counted."""
    return (3.0 * scan_forward_flops_per_token(cfg, chunk) * batch * seq
            * layers_of(cfg, "ssm"))


def matmul_params_per_token(cfg) -> int:
    """Parameters that sit in a matrix multiplication for EVERY token,
    whatever the router does: a state-space mixer's two projections (D x
    (2 inner + 2 G N + H) and inner x D), an attention mixer's four (q and o at
    the query heads, k and v at the KV heads), per routed block the router
    (all experts wide) and the shared expert's two matrices, the untied output
    head over the vocabulary held here. NOT counted: the embedding's lookup,
    gains, taps, biases, A, D, and the routed experts
    (``train_flops_per_token`` counts the rows they really compute)."""
    d = cfg.d_model
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    ssm_in = 2 * inner + 2 * cfg.ssm_groups * cfg.ssm_state + cfg.ssm_heads
    mixers = (layers_of(cfg, "ssm") * d * (ssm_in + inner)
              + layers_of(cfg, "attn") * 2 * d * cfg.head_dim * (cfg.n_heads + cfg.kv_heads))
    routed = cfg.routed_layers * d * (cfg.n_experts + 2 * cfg.moe_shared_expert_ff)
    return mixers + routed + d * cfg.vocab_size


def attn_core_flops_per_step(cfg, batch: int, seq: int) -> float:
    """Operations the causal attention cores of the "attn" blocks REQUIRE in a
    training step, 2 per multiply-add, over the visible (query, key) pairs
    (seq (seq + 1) / 2 a sequence and head): forward Q K^T and P V; backward
    its own four products, twice the forward's. NOT counted: the masked half
    of a diagonal block, a flash backward's recomputed scores, the softmax."""
    pairs = seq * (seq + 1) / 2.0
    per_layer = float(batch) * cfg.n_heads * pairs * 2 * cfg.head_dim * 2
    return 3.0 * per_layer * layers_of(cfg, "attn")


def held_gemm_flops_per_step(cfg, held_rows_per_step: float) -> float:
    """Operations the grouped GEMMs of one training step require on ONE
    RANK'S SHARE of UNGATED experts: six products a routed block of 2 x rows x
    d_model x expert width, rows = the token-choices that fell on the experts
    held here, summed over the routed blocks (``held_rows_per_step``: the
    program's ``moe_held_rows``). NOT counted: rows padded up to a tile, tiles
    computed for a short group, the forward products computed again under
    remat, the shared expert (a plain matmul)."""
    return GROUPED_GEMMS_PER_LAYER * 2.0 * held_rows_per_step * cfg.d_model * cfg.ff_dim


def held_gemm_bytes_per_step(cfg, held_rows_per_step: float,
                             bytes_per_elem: int = 2) -> float:
    """The least the same six products move through HBM: each reads or writes
    its two row operands once (held rows x d_model and held rows x expert
    width) and the HELD experts' matrix of that projection once in every
    routed block, at ``bytes_per_elem`` (bf16). NOT counted: a weight tile
    read again for every row tile, float32 accumulators, the transposes around
    the weight-gradient kernel, recomputation."""
    rows = held_rows_per_step * (cfg.d_model + cfg.ff_dim)
    weights = cfg.routed_layers * cfg.experts_held * cfg.d_model * cfg.ff_dim
    return GROUPED_GEMMS_PER_LAYER * float(rows + weights) * bytes_per_elem


def train_flops_per_token(cfg, seq: int, held_rows_per_token: float,
                          chunk: int = SCAN_CHUNK) -> float:
    """Forward + backward operations per trained token: 6 per matmul parameter
    every token meets (``matmul_params_per_token``), plus 6 x the TWO expert
    matrices (d_model x expert width) x the expert rows this chip computed per
    token (``held_rows_per_token``: held rows summed over the routed blocks,
    over the tokens: what the held share really multiplied, NOT k a block),
    plus the attention blocks' causal cores (``attn_core_flops_per_step`` of
    one sequence, over its tokens), plus 3 x the scans' forward operations
    (``scan_forward_flops_per_token`` a state-space layer). Recomputation
    (remat), the convolution, the gates and the grouped norm's elementwise
    passes, the router's top-k, sorts, gathers, softmaxes and pad rows are not
    counted."""
    experts = 6.0 * 2 * cfg.d_model * cfg.ff_dim * held_rows_per_token
    scans = 3.0 * scan_forward_flops_per_token(cfg, chunk) * layers_of(cfg, "ssm")
    return (6.0 * matmul_params_per_token(cfg) + experts
            + attn_core_flops_per_step(cfg, 1, seq) / seq + scans)
