"""The benchmark's arithmetic for a sparse window / full attention stack whose
every layer is routed, with no shared expert and no dense layer (SmallThinker
shaped): the parameter counts from the source's keys, which the configuration's
file states and the tests hold the program's tree to, and the forward pass's
matmul operations by part, which the cell's ``why`` quotes. The operations a
trained token requires, the cores' visible pairs and bytes are ``arith_swa``'s
as they are (it reads heads, window, layers, dense and shared widths from the
configuration: with no dense layer and no shared expert its terms are this
stack's), and the held experts' grouped GEMMs ``arith_mla.held_gemm_*``'s: the
cell reports ``swa_active_mfu_pct`` and the accepted roofline shares, no count
of its own. ``cfg`` is the program's ``TransformerConfig``; ``src`` the
source's ``config.json`` as a dict.
"""

from __future__ import annotations

from chipbench import arith_swa


def layer_parameters(src: dict, experts: int = None) -> int:
    """Every parameter of ONE block as the source's keys give it: q and o at
    the query heads, k and v at the KV heads, the router over ALL the primary
    experts, ``experts`` experts (default: all of them) of three matrices, two
    norm gains. The same for a full and a window layer."""
    d, dh = src["hidden_size"], src["head_dim"]
    attn = 2 * d * dh * (src["num_attention_heads"] + src["num_key_value_heads"])
    held = src["moe_num_primary_experts"] if experts is None else experts
    return (attn + d * src["moe_num_primary_experts"]
            + held * 3 * d * src["moe_ffn_hidden_size"] + 2 * d)


def parameters(src: dict, layers: int = None, experts: int = None,
               vocab: int = None) -> int:
    """Every parameter of the model at ``layers`` blocks (default
    ``num_hidden_layers``), ``experts`` experts a block (default all) and
    ``vocab`` rows (default ``vocab_size``): the blocks, the embedding and the
    untied head, the final norm."""
    L = src["num_hidden_layers"] if layers is None else layers
    V = src["vocab_size"] if vocab is None else vocab
    return L * layer_parameters(src, experts) + 2 * V * src["hidden_size"] + src["hidden_size"]


def forward_matmul_flops_per_token(cfg, seq: int, held_rows_per_token: float) -> dict:
    """The forward pass's matmul operations a token by part (the cell's
    ``why`` quotes their shares): the cores by kind, the projections and
    routers, the held experts, the head."""
    d = cfg.d_model
    fwd = lambda m: arith_swa.core_flops_per_step(cfg, m, 1, seq) / 3.0 / seq
    head = 2.0 * d * cfg.vocab_size
    return {"full_cores": fwd("attn"), "window_cores": fwd("swa"),
            "projections": 2.0 * arith_swa.matmul_params_per_token(cfg) - head,
            "held_experts": 2.0 * 3 * d * cfg.ff_dim * held_rows_per_token,
            "head": head}
