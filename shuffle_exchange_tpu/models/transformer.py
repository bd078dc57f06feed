"""Decoder-only transformer family, TPU-first.

This is the model zoo used by the benchmarks and the serving engine — the
capability analog of the reference's supported architectures
(``module_inject/containers/`` gpt2/llama/llama2 etc., and
``inference/v2/model_implementations/llama_v2/model.py``), built the JAX way:

- **Scanned layers**: per-layer params are stacked on a leading dim and the
  layer body runs under ``lax.scan`` — O(1) compile time in depth, natural
  remat boundaries, and the stack dim later doubles as the pipeline-stage
  dim.
- **Mesh-aware partition specs**: every weight carries a logical
  PartitionSpec (heads/ffn over "tensor", vocab over "tensor") — the AutoTP
  analog (module_inject/auto_tp.py): XLA inserts the row/column-parallel
  collectives the reference implements as LinearLayer/LinearAllreduce
  (module_inject/layers.py:388,465).
- bf16-friendly: params live in the engine's train dtype; norms/softmax/CE
  computed in fp32.

Configs cover GPT-2 (learned pos, LayerNorm, GELU) and Llama-3 (RoPE,
RMSNorm, SwiGLU, GQA) families plus tiny test sizes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

from ..profiling import trace


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None          # None = MHA; < n_heads = GQA
    d_ff: Optional[int] = None                 # default 4*d (gelu) or 8/3*d (swiglu)
    max_seq_len: int = 2048
    activation: str = "gelu"                   # "gelu" | "swiglu" | "reglu" | ... (gate_fn,
                                               # activation_fn)
    norm: str = "layernorm"                    # "layernorm" | "rmsnorm"
    position: str = "learned"                  # "learned" | "rope" | "alibi" | "none" (no
                                               # position signal at all: attention among
                                               # mixers that carry the order, ``_gqa``)
    rope_theta: float = 500000.0
    tie_embeddings: bool = True
    dropout: float = 0.0
    norm_eps: float = 1e-5
    attn_qkv_bias: bool = False                # Qwen2-style q/k/v biases
    attn_out_bias: bool = False                # GPT-2/OPT-style out-proj bias
    pos_offset: int = 0                        # OPT offsets positions by 2
    qk_norm: Any = False                       # True (OLMoE's, Olmo 2 / 3's): RMSNorm
                                               # (learned gain) over the WHOLE q and k
                                               # projections, before heads and RoPE
                                               # (mixers "attn" and "swa": ``_gqa``).
                                               # "head" (LFM2's): RMSNorm per HEAD over its
                                               # head_dim, one gain [head_dim] each for q and
                                               # k shared by the heads, before RoPE (mixer
                                               # "attn": ``_gqa``).
                                               # (mixer "gated_attn" always norms per head,
                                               # by the block norm's kind: Qwen3-Next's.)
    # Family structure flags (round 3, HF import breadth — reference
    # module_inject/containers/{gptj,gptneox,bloom}.py + falcon in
    # inference/v2/engine_factory.py):
    parallel_block: bool = False               # h + attn(y1) + mlp(y2) (GPT-J/NeoX/Falcon)
    parallel_shared_ln: bool = False           # y2 = y1, no ln2 (GPT-J, Falcon-7B)
    rotary_dim: int = 0                        # rope on first rotary_dim dims (0 = all)
    rope_interleaved: bool = False             # GPT-J rotate-every-two pairs
    embed_ln: bool = False                     # BLOOM word_embeddings_layernorm
    alibi_slope_scale: float = 1.0             # falcon scales alibi by 1/sqrt(Dh)
    mlp_bias: bool = True                      # gelu-path fc biases (False: Falcon)
    unembed_bias: bool = False                 # GPT-J lm_head bias
    # Random-LTD (reference runtime/data_pipeline/data_routing): middle
    # layers skip a random token subset per step. TPU (static-shape) form:
    # dropped tokens FREEZE their hidden state through the layer (masked
    # select) instead of being gathered out — same schedule/regularization,
    # no dynamic shapes. They remain visible as keys, a documented deviation.
    random_ltd: bool = False
    random_ltd_start_layer: int = 1
    random_ltd_end_layer: int = -1             # exclusive; -1 = n_layers - 1
    # Encoder-family structure (round 4, reference module_inject/containers/
    # bert.py + distil_bert.py): bidirectional attention, post-LN residual
    # order, token-type embeddings, and the BERT MLM head
    # (transform dense + LN + tied decoder with its own bias).
    causal: bool = True                        # False = bidirectional (BERT)
    post_ln: bool = False                      # LN(h + sublayer) (BERT)
    type_vocab_size: int = 0                   # token_type embeddings (BERT)
    mlm_head: bool = False                     # BertForMaskedLM cls head
    # GPT-Neo structure (reference module_inject/containers/gptneo.py):
    # unscaled attention + alternating global/local layers.
    attn_scale: float = 0.0                    # 0 = 1/sqrt(Dh); GPT-Neo: 1.0;
                                               # Granite's attention_multiplier
    local_attention_window: int = 0            # window for "local" layers
    attention_pattern: Tuple[str, ...] = ()    # per-layer "global"/"local",
                                               # cycled over n_layers: a flag
                                               # of ONE kind of layer over a
                                               # dense [T, T] mask (GPT-Neo,
                                               # hf.py's import of it; short
                                               # contexts). Window layers
                                               # with shapes of their own,
                                               # through the kernels: mixer
                                               # "swa" of a layer_pattern
    dtype: Any = None                          # compute dtype override (engine usually casts)
    remat: bool = False
    remat_policy: str = "dots_saveable"
    # MoE (reference moe/layer.py MoE wrapper; Mixtral-style when set)
    n_experts: int = 0                         # 0 = dense
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    moe_impl: str = "auto"   # auto | capacity (index dispatch) | capacity_einsum | ragged (dropless)
    moe_shared_expert_ff: int = 0              # Qwen2-MoE shared expert (0 = none)
    moe_norm_topk: bool = True                 # renormalize top-k weights (Mixtral);
                                               # False = raw softmax probs (Qwen2-MoE)
    # "first_choice": DeepSpeed's l_aux (first choice only), summed over
    # layers. "all_choices": HF load_balancing_loss_func (OLMoE, Mixtral's HF
    # form): E * sum_e f[e] * P[e] with f counting all k choices and both
    # means taken over the tokens of ALL layers together. "sequence":
    # DeepSeek-V3's sequence-wise balance loss, a layer's own, summed over the
    # routed layers (``aux_loss_coef`` is its alpha). "none": no loss.
    moe_aux: str = "first_choice"
    # Megatron --expert-interval interleaving: per-layer MoE flags, cycled
    # over n_layers; () = every layer is MoE (when n_experts > 0). Dense
    # layers store their FFN in expert slot 0 of the stacked arrays and a
    # traced per-layer flag selects the dense path inside the scan. Like
    # attention_pattern a variation of ONE kind of layer (the dense FFN has
    # an expert's shapes); layers that share no shapes are a layer_pattern.
    moe_layer_pattern: Tuple[bool, ...] = ()
    attention_impl: str = "auto"
    # Chunked vocab CE (reference FPDT chunked logits loss,
    # sequence/fpdt_layer.py:1137): compute the loss, and its gradient in the
    # same pass, in seq chunks so [B, T, vocab] logits are never
    # materialized. 0 = full logits; > 0 = positions a chunk; -1 = auto (chunk
    # when the full float32 logits pass LOSS_CHUNK_BYTES, in chunks whose
    # ROWS, batch x positions, stay under it).
    loss_chunk: int = -1
    # Pad the chunked-loss unembed to a 128-multiple vocab (MXU lane tile)
    # with -1e30-masked pad columns. None = auto (TPU, unaligned vocab only).
    pad_vocab_logits: Optional[bool] = None
    # Sequence-parallel attention flavor when the mesh has seq > 1:
    # "ulysses" (a2a seq<->head reshard around the local attention_impl
    # kernel) or "ring" (KV blocks rotate via ppermute — the context-
    # parallel form; no head-count divisibility requirement). Ring is its
    # own chunked online-softmax (attention_impl is not used); each hop is
    # checkpointed, so backward residuals are O(T/sp * D) per layer
    # (score tiles are recomputed hop by hop, never saved).
    sp_attention: str = "ulysses"
    # Ring-CP tuning (ISSUE 15; set by sxt.initialize from the engine
    # config's context_parallel section): per-hop KV tile for the jnp
    # chunked ring, and the hop-kernel routing ("auto" gates on shape/
    # backend, "pallas" forces the flash_attention_lse hop kernel,
    # "xla" keeps the jnp chunked online-softmax).
    cp_kv_chunk: int = 1024
    cp_use_kernel: str = "auto"
    # Width of an attention head; 0 = d_model // n_heads (Qwen3-Next: 256
    # against 2048 / 16). Read it as ``head_dim``.
    head_size: int = 0
    # The stack as a PERIOD of layer kinds, each (mixer, ffn), cycled over
    # n_layers (which the period must divide); () = one kind, ("attn", "moe"
    # if n_experts else "mlp"): every model before Qwen3-Next. ``stack_apply``
    # scans over periods and each kind's parameters are stacked on their own
    # (``params["layers"][kind]``, [periods, layers of the kind a period, ...];
    # a one-kind model keeps the flat ``params["layers"]`` [n_layers, ...]).
    #   mixer "attn"        softmax attention as every flag above shapes it
    #                       (``_gqa``: n_heads, the model's table), whatever
    #                       the stack's other layers are
    #         "swa"         the same over a window, a kind of its own:
    #                       swa_window / swa_heads / swa_rope_* below
    #         "gated_attn"  Qwen3-Next full attention: q and a sigmoid output
    #                       gate from one projection, per-HEAD q/k RMSNorm
    #                       (the block norm's kind) before RoPE
    #         "gdn"         Gated DeltaNet (ops/gated_delta.py), gdn_* below
    #         "kda"         the delta rule with a decay a key channel (Kimi
    #                       Delta Attention, ops/kda.py), kda_* below
    #         "sconv"       LFM2's gated short convolution (ops/short_conv.py):
    #                       one projection to [gate B | gate C | x], a causal
    #                       depthwise convolution of ``sconv_taps`` taps over
    #                       B * x, C * that, one projection back; no heads, no
    #                       RoPE, no state but the last taps - 1 rows
    #         "mla"         latent attention, mla_* below
    #         "ssm"         the Mamba-2 state-space layer (ops/ssd.py), ssm_*
    #                       below: one projection to [z | x B C | dt], a causal
    #                       depthwise convolution with a bias and SiLU over
    #                       x B C, the scan, a gated grouped RMSNorm, one
    #                       projection back; no RoPE
    #   q/k norm: "attn" takes ``qk_norm`` (True = the whole projection;
    #   "head" = per head); "swa" the whole-projection one; "gated_attn"
    #   norms per head always; "mla", "gdn", "kda", "sconv" and "ssm" have none.
    #   rotation: "attn" rotates by the model's table where ``position`` is
    #   "rope", and by nothing where it is "none" (Nemotron-H: the state-space
    #   layers carry the order).
    #   ffn   "mlp" | "moe" | "none": a layer that is a mixer ALONE (one norm,
    #   one residual step, no ffn leaves: Nemotron-H's ``M*``, a state-space
    #   layer followed at once by attention)
    # ``attention_pattern`` (GPT-Neo) and ``moe_layer_pattern`` (Megatron)
    # are older and stay as they are: they vary a FLAG of one kind whose
    # layers share every shape (the other variant lives in the same arrays),
    # and only a one-kind model may use them. A pattern is for layers that
    # share no shapes.
    layer_pattern: Tuple[Tuple[str, str], ...] = ()
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0                       # per key head (q and k)
    gdn_value_dim: int = 0                     # per value head
    gdn_conv_kernel: int = 4
    # The write strength is ``gdn_beta_scale`` x sigmoid: 1.0 keeps the
    # transition ``I - beta k k^T``'s eigenvalues in (0, 1) (Qwen3-Next), 2.0
    # lets them reach (-1, 1) (FLA's ``allow_neg_eigval``, Olmo Hybrid's
    # ``linear_allow_neg_eigval``).
    gdn_beta_scale: float = 1.0
    # mixer "kda" (Kimi Linear's ``linear_attn_config``): ``kda_heads`` heads
    # of ``kda_key_dim`` (q and k) and ``kda_value_dim``, as many value heads
    # as key heads; three projections (one leaf, [q | k | v]) each through a
    # causal depthwise convolution of ``kda_conv_kernel`` taps without bias;
    # the decay, a log-decay for every KEY CHANNEL, and the output gate (a
    # sigmoid) each through a low-rank pair of ``kda_gate_rank`` columns.
    kda_heads: int = 0
    kda_key_dim: int = 0
    kda_value_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_rank: int = 0
    # Where a block norms: "input" (pre-norm, ``h + mix(norm(h))``),
    # "output" (the Olmo 2 / 3 order: ``h + norm(mix(h))``, ``h +
    # norm(ffn(h))``, nothing normed on the way in) or "sandwich" (both at
    # once, Ouro's: ``h + norm(mix(norm(h)))``, ``h + norm(ffn(norm(h)))``).
    # The same gains (``ln1_w`` / ``ln2_w``) and scopes (``attn_norm`` /
    # ``mlp_norm``) in the first two; a sandwich keeps them for the way in
    # and has two more for the way out (``ln1_post_w`` / ``ln2_post_w``),
    # under the same scopes.
    # (``post_ln`` is BERT's ``norm(h + f(h))`` and ``parallel_block`` GPT-J's
    # ``h + mix(norm(h)) + ffn(norm(h))``: a block has ONE of the forms,
    # ``Transformer.__init__``.)
    norm_order: str = "input"
    # A stack that runs several times over the SAME weights (Ouro's
    # ``total_ut_steps``): ``h_t = final_norm(stack(h_{t-1}))`` for t = 1 ..
    # ``loop_steps``, the final norm INSIDE the loop (what the next step, the
    # head and the gate read); 1 = once, the final norm in the head: every
    # model before PR 64. ``Transformer.loop_apply`` is the outer scan; a
    # layer's weight gradient is the sum over its ``loop_steps`` visits.
    loop_steps: int = 1
    # The exits of a looped stack (``loop_steps`` > 1). Without a gate the
    # loss is the last exit's. ``exit_gate``: one Linear(D, 1) WITH bias
    # (``exit_gate_w`` [D], ``exit_gate_b`` []) reads every exit's stream,
    # ``lam_t = sigmoid(.)``, and each token leaves at exit t with
    # ``p_t = lam_t prod_{j<t} (1 - lam_j)`` (the last exit takes what is
    # left; its own lam is not used). The loss is then the mean over tokens
    # of ``sum_t p_t CE_t - exit_entropy_coef x H(p)`` (the report's Stage-I
    # objective: the expected task loss under the exit distribution, less
    # ``beta`` x its entropy), in float32, gradients through p AND CE.
    exit_gate: bool = False
    exit_entropy_coef: float = 0.0
    sconv_taps: int = 3                        # mixer "sconv": the convolution's taps
    # mixer "ssm": ``ssm_heads`` heads of ``ssm_head_dim`` channels (the inner
    # width is their product, whatever d_model is), B and C of ``ssm_groups``
    # groups of ``ssm_state`` (head h reads group h // (heads / groups)), the
    # convolution's taps over the inner width + 2 x groups x state channels
    # (the scan's chunk is the algorithm's, not the model's: ``ops/ssd.CHUNK``)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv_kernel: int = 4
    # One expert-parallel rank's share of a routed layer: the router scores
    # all ``n_experts``, this model holds ``n_experts_held`` of them (0 = all)
    # starting at ``expert_first`` and computes only the token-choices that
    # fall on those (moe/layer.expert_mlp_ragged; dropless impl only). Their
    # rows go to a buffer of ``moe_held_rows_factor`` x the balanced share
    # (tokens x k x held / n_experts); a row that does not fit is dropped and
    # counted (``moe_overflow_rows``). 3 is what ``qwen3next-train`` runs: an
    # untrained router over Zipf ids reads up to 1.1 x the balanced share
    # there and nothing overflows (PERF.md section 6, PR 33). The factor buys
    # room in memory (the buffer's rows, and a write of zeros a pass), not
    # time in the row passes: those walk the blocks that hold a row
    # (``moe_visited_rows``; PERF.md section 6, PR 44).
    n_experts_held: int = 0
    expert_first: int = 0
    moe_held_rows_factor: float = 3.0
    # Latent attention (DeepSeek-V2/V3 MLA; mixer "mla" of a layer_pattern or
    # of lead_kind): q [H x (content + rope)] straight from the block input
    # (no query compression), ONE down-projection to a latent of
    # ``mla_kv_rank`` and one rotary key of ``mla_qk_rope_dim`` a token, an
    # RMSNorm on the latent, an up-projection to per-head content keys and
    # values. Scores are (content + rope) wide, values ``mla_v_dim``; RoPE
    # (``rotary_dim`` = the rope dims, ``rope_interleaved`` as the source
    # stores them) turns the rope dims only. ``head_size`` = content + rope.
    mla_kv_rank: int = 0
    mla_qk_content_dim: int = 0
    mla_qk_rope_dim: int = 0
    mla_v_dim: int = 0
    # Leading layers: ``lead_layers`` layers of ``lead_kind`` (mixer, ffn)
    # come before the periods of ``layer_pattern`` (DeepSeek's
    # first_k_dense_replace: dense layers before the routed ones). They are
    # stacked on their own (``params["lead"]`` [lead_layers, ...]) and run in
    # a scan of their own; ``n_layers`` counts them, and the rest must be
    # whole periods. 0 = none: every model before PR 35.
    lead_layers: int = 0
    lead_kind: Tuple[str, str] = ()
    # Width of a dense ("mlp") layer's FFN where the model also has experts
    # of width ``d_ff`` (0 = d_ff too).
    dense_ff: int = 0
    # How a shared expert joins: "sigmoid" = through a per-token sigmoid gate
    # (Qwen2-MoE, Qwen3-Next), "none" = added as it is (DeepSeek-V3).
    moe_shared_gate: str = "sigmoid"
    # The router's score (gating.topk_select): "softmax" over the experts, or
    # "sigmoid" of each logit (DeepSeek-V3). ``moe_select_bias``: a per-expert
    # bias added to the scores for the CHOICE only, not weighed
    # (``moe_select_bias`` leaf; the aux-free balancing buffer: no gradient).
    # ``moe_weight_scale`` multiplies the combine weights. The bias is a
    # BUFFER, not a weight: the optimizer's update of it is thrown away
    # (``Transformer.update_buffers``) and after each step it moves by
    # ``moe_bias_update_rate`` (DeepSeek-V3's bias update speed gamma; 0: held
    # fixed) towards balance: down where an expert of the step's batch got
    # more than the mean of the token-choices, up where it got fewer.
    moe_score: str = "softmax"
    moe_select_bias: bool = False
    moe_weight_scale: float = 1.0
    moe_bias_update_rate: float = 0.0
    # Windowed softmax attention as a KIND of layer (mixer "swa" of a
    # layer_pattern, beside "attn" layers that see everything before them):
    # key j is visible to query i iff 0 <= i - j < ``swa_window`` (itself and
    # the window - 1 before it). The kind has its own head count
    # (``swa_heads`` query heads over the model's ``kv_heads``; 0 = n_heads)
    # and so its own projection shapes, and its own RoPE table
    # (``swa_rope_theta``, 0 = rope_theta; ``swa_rotary_dim`` leading dims of
    # a head, 0 = all), built once outside the layer scans. The window reaches
    # the attention kernels as a local causal mask whose empty blocks they
    # skip (ops/flash_attention ``window``): no [T, T] mask exists at any size.
    # (``local_attention_window`` / ``attention_pattern`` above are GPT-Neo's
    # older form: a FLAG of one kind over a dense mask.)
    swa_window: int = 0
    swa_heads: int = 0
    swa_rope_theta: float = 0.0
    swa_rotary_dim: int = 0
    # Mixers of a layer_pattern that rotate NOTHING in a model whose
    # ``position`` is "rope" (SmallThinker: ("attn",), full layers that mark no
    # position beside window layers that rotate by the model's own table).
    # Such a layer is handed ``rope`` (None, None) (``rope_for``) and opens the
    # scopes ``nope_qkv`` / ``nope_core`` / ``nope_out`` inside the attention
    # layer's. (``position`` "none" is the same for the WHOLE model.)
    unrotated_mixers: Tuple[str, ...] = ()
    # What a routed block's router reads: "ffn" = what its experts read, the
    # post-attention norm (every model before PR 57); "block" = the block's
    # INPUT, un-normed, as it was before the mixer (SmallThinker: the choice
    # can be made, and the experts fetched, while attention runs). On the
    # dropless "ragged" impl.
    moe_router_input: str = "ffn"
    # A learned sparse attention (mixer "dsa" of a layer_pattern: Keye-VL-2.0's
    # ``sa_config``, DeepSeek-Sparse-Attention shaped; ``ops/dsa``): an indexer
    # of ``dsa_index_heads`` heads of ``dsa_index_dim`` over ONE key head scores
    # every earlier key from the DETACHED block input, each query keeps its
    # ``dsa_topk`` best (one set for all its heads, no gradient) and the GQA
    # softmax runs over those alone. ``loss`` adds the indexer's own loss at
    # coefficient 1: the mean over layers and tokens of the KL from the main
    # attention's head-averaged probabilities to the softmax of the indexer's
    # scores over the chosen keys; it reaches the indexer's leaves only
    # (``dsa_wq`` / ``dsa_wk`` / ``dsa_ww`` / ``dsa_k_norm_w`` / ``dsa_k_norm_b``)
    # and the other losses reach every leaf but those.
    dsa_topk: int = 0
    dsa_index_heads: int = 0
    dsa_index_dim: int = 0
    # M-RoPE (``rope_scaling.mrope_section``, the chunked layout): the rotated
    # pairs are split into len(mrope_section) runs and run i takes its angle
    # from position stream i (temporal, height, width) of ``position_ids``
    # [3, B, T]; () = one stream. On text the streams are equal and the table
    # is ``rope_table``'s, bit for bit.
    mrope_section: Tuple[int, ...] = ()
    # YaRN on the model's own RoPE table (the "attn" layers'; ``rope_table``):
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor), () = unscaled. The inverse frequencies are blended
    # between interpolation (/ factor) and extrapolation by the ramp
    # transformers' ``_compute_yarn_parameters`` builds over the ``rotary_dims``
    # rotated dims, and cos and sin carry the attention factor.
    rope_yarn: Tuple[float, ...] = ()
    # The Granite family's multipliers (with ``attn_scale``, its
    # attention_multiplier, the four of them); 1 = none, and a neutral one
    # emits no operation. ``embed_scale`` multiplies the looked-up rows
    # (embedding_multiplier; a tied head reads the rows unscaled),
    # ``residual_scale`` each sublayer's output before it is added to the
    # stream (residual_multiplier: ``h + r * mix(norm(h))``, ``h + r *
    # ffn(norm(h))``), ``logit_divisor`` divides
    # the logits (logits_scaling), in ``head`` and inside the chunked loss,
    # forward and backward. Each is applied in float32 to the value as its
    # producer left it, and the result rounded once.
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_divisor: float = 1.0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def several_kinds(self) -> bool:
        """The stack has layers of more than one (mixer, ffn) kind."""
        return len(set(self.kinds_used)) > 1

    def heads_of(self, mixer: str) -> int:
        """Query heads of a layer whose mixer is ``mixer``."""
        return self.swa_heads or self.n_heads if mixer == "swa" else self.n_heads

    @property
    def rotary_dims(self) -> int:
        return self.rotary_dim or self.head_dim

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def pattern(self) -> Tuple[Tuple[str, str], ...]:
        """The period of (mixer, ffn) kinds; one entry for a one-kind model."""
        return tuple(tuple(kind) for kind in self.layer_pattern) or (
            ("attn", "moe" if self.n_experts > 0 else "mlp"),)

    @property
    def kinds_used(self) -> Tuple[Tuple[str, str], ...]:
        """Every (mixer, ffn) some layer has: the leading kind and the period's."""
        lead = (tuple(self.lead_kind),) if self.lead_layers else ()
        return lead + self.pattern

    @property
    def recurrent(self) -> bool:
        """Some layer carries a recurrent state instead of a KV cache."""
        return any(mixer in ("gdn", "kda", "ssm") for mixer, _ in self.kinds_used)

    @property
    def latent(self) -> bool:
        """Some layer attends through a latent (MLA) instead of k and v."""
        return any(mixer == "mla" for mixer, _ in self.kinds_used)

    @property
    def routed_layers(self) -> int:
        """Layers whose FFN is routed: the rows of the routing counters."""
        if self.n_experts <= 0:
            return 0
        period = self.pattern
        periods = (self.n_layers - self.lead_layers) // len(period)
        lead = self.lead_layers if self.lead_layers and self.lead_kind[1] == "moe" else 0
        return lead + periods * sum(1 for _, ffn in period if ffn == "moe")

    def layers_of(self, mixer: str) -> int:
        """Layers whose mixer is ``mixer``, the leading ones counted."""
        period = self.pattern
        periods = (self.n_layers - self.lead_layers) // len(period)
        lead = self.lead_layers if self.lead_layers and self.lead_kind[0] == mixer else 0
        return lead + periods * sum(1 for m, _ in period if m == mixer)

    @property
    def ssm_layers(self) -> int:
        """Layers whose mixer is the state-space one: the scans a step walks."""
        return self.layers_of("ssm")

    @property
    def gdn_layers(self) -> int:
        """Layers whose mixer is the Gated DeltaNet: the rules a step walks."""
        return self.layers_of("gdn")

    @property
    def kda_layers(self) -> int:
        """Layers whose mixer is the delta rule with a decay a key channel."""
        return self.layers_of("kda")

    @property
    def dense_ff_dim(self) -> int:
        return self.dense_ff or self.ff_dim

    @property
    def ff_dim(self) -> int:
        if self.d_ff:
            return self.d_ff
        if self.activation == "swiglu":
            # Llama convention: 2/3 * 4d rounded to multiple of 256
            d = int(8 * self.d_model / 3)
            return 256 * ((d + 255) // 256)
        return 4 * self.d_model


# ---------------------------------------------------------------------------
# Presets (sizes match the capability configs BASELINE.json lists)
# ---------------------------------------------------------------------------

def gpt2_small() -> TransformerConfig:  # 125M — capability config #1
    return TransformerConfig(vocab_size=50257, d_model=768, n_layers=12, n_heads=12,
                             max_seq_len=1024, activation="gelu", norm="layernorm", position="learned",
                             attn_qkv_bias=True, attn_out_bias=True)


def gpt2_large() -> TransformerConfig:
    return TransformerConfig(vocab_size=50257, d_model=1280, n_layers=36, n_heads=20,
                             max_seq_len=1024, activation="gelu", norm="layernorm", position="learned",
                             attn_qkv_bias=True, attn_out_bias=True)


def llama3_8b() -> TransformerConfig:  # capability config #2 (north star)
    return TransformerConfig(vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
                             d_ff=14336, max_seq_len=8192, activation="swiglu", norm="rmsnorm",
                             position="rope", rope_theta=500000.0, tie_embeddings=False)


def llama3_70b() -> TransformerConfig:  # capability config #4
    return TransformerConfig(vocab_size=128256, d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
                             d_ff=28672, max_seq_len=8192, activation="swiglu", norm="rmsnorm",
                             position="rope", tie_embeddings=False)


def mixtral_8x7b() -> TransformerConfig:  # capability config #3
    return TransformerConfig(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
                             d_ff=14336, max_seq_len=8192, activation="swiglu", norm="rmsnorm",
                             position="rope", rope_theta=1e6, tie_embeddings=False,
                             n_experts=8, moe_top_k=2)


def tiny(vocab=256, d=64, layers=2, heads=4, seq=64, **kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab, d_model=d, n_layers=layers, n_heads=heads,
                             max_seq_len=seq, **kw)


def tiny_moe(vocab=256, d=64, layers=2, heads=4, seq=64, experts=4, **kw) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab, d_model=d, n_layers=layers, n_heads=heads,
                             max_seq_len=seq, activation="swiglu", norm="rmsnorm", position="rope",
                             n_experts=experts, moe_top_k=2, **kw)


# ---------------------------------------------------------------------------
# Core ops (jnp reference implementations; Pallas kernels swap in via ops/)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gelu(approximate: bool):
    """``jax.nn.gelu`` whose backward keeps the pre-activation alone and
    replays the chain (``jax.vjp`` of the same text, so the gradient is plain
    autodiff's): autodiff keeps the FIVE values inside the tanh form beside
    it, which a layer scan with no checkpointing stacks for every layer
    (``gpt2m-train``: 4.0 of the 8.7 GB a step, PR 58). A ``custom_vjp`` and
    not ``jax.checkpoint``: the decode kernels' bodies call this too, and
    Mosaic lowers the one and not the other."""
    import jax

    plain = functools.partial(jax.nn.gelu, approximate=approximate)

    @jax.custom_vjp
    def gelu(x):
        return plain(x)

    gelu.defvjp(lambda x: (plain(x), x), lambda x, g: jax.vjp(plain, x)[1](g))
    return gelu


def activation_fn(name: str):
    """Non-gated activation dispatch (the gated "swiglu" and "reglu" are
    handled structurally: ``gate_fn``).

    "gelu" is the exact (erf) form as in HF; "gelu_new"/"gelu_pytorch_tanh"
    are the tanh approximation (GPT-2 lineage); all three save their input
    for the backward and nothing else (``_gelu``)."""
    import jax

    if name in ("gelu", "gelu_new", "gelu_pytorch_tanh"):
        return _gelu(name != "gelu")
    try:
        return {"relu": jax.nn.relu, "silu": jax.nn.silu,
                # squared ReLU (Nemotron-H's ``relu2``): ungated, W2 relu(W1 y)^2
                "relu2": lambda x: jax.numpy.square(jax.nn.relu(x))}[name]
    except KeyError:
        raise ValueError(f"Unsupported activation {name!r}; use swiglu/reglu/gelu/relu/relu2/silu/gelu_new")


def gate_fn(name: str):
    """The nonlinearity on the gate of a GATED feed-forward unit, ``act(x Wg)
    * (x Wu)``: SiLU for "swiglu", ReLU for "reglu" (SmallThinker's experts);
    None for an ungated activation (``activation_fn``'s). A gated unit has a
    third matrix, so this decides shapes as well as arithmetic."""
    import jax

    return {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}.get(name)


@functools.lru_cache(maxsize=None)
def _layernorm(eps: float):
    """LayerNorm over the last axis, float32 inside, whose backward keeps what
    it was GIVEN (x in the dtype it arrived in, the gain) and two float32
    numbers a row (the mean and ``1 / sqrt(var + eps)``), and recomputes the
    centred and the scaled copy from them: plain autodiff keeps three float32
    arrays of x's shape a call, which a layer scan with no checkpointing
    stacks for every layer (``gpt2m-train``: 2.4 of the 8.7 GB a step, PR
    58). The forward is the plain text's, operation for operation."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    def fwd(x, weight, bias):
        x32 = x.astype(f32)
        mean = x32.mean(-1, keepdims=True)
        rstd = 1.0 / jnp.sqrt(x32.var(-1, keepdims=True) + eps)
        out = (x32 - mean) * rstd
        out = out * weight.astype(f32) + bias.astype(f32)
        return out.astype(x.dtype), (x, weight, bias, mean, rstd)

    @jax.custom_vjp
    def layernorm(x, weight, bias):
        return fwd(x, weight, bias)[0]

    def bwd(res, g):
        x, weight, bias, mean, rstd = res
        g32 = g.astype(f32)
        xhat = (x.astype(f32) - mean) * rstd
        rows = tuple(range(g.ndim - 1))
        gw = g32 * weight.astype(f32)
        dx = rstd * (gw - gw.mean(-1, keepdims=True)
                     - xhat * (gw * xhat).mean(-1, keepdims=True))
        return (dx.astype(x.dtype),
                (g32 * xhat).sum(rows).reshape(weight.shape).astype(weight.dtype),
                g32.sum(rows).reshape(bias.shape).astype(bias.dtype))

    layernorm.defvjp(fwd, bwd)
    return layernorm


def _norm(x, weight, bias, kind: str, eps: float = 1e-5):
    import jax.numpy as jnp

    if kind in ("rmsnorm", "rmsnorm_zc"):
        from ..ops.rmsnorm import rmsnorm

        # "rmsnorm_zc": a zero-centred gain, x / rms(x) * (1 + w) (Qwen3-Next)
        x32 = x.astype(jnp.float32)
        gain = weight.astype(jnp.float32)
        return rmsnorm(x32, 1.0 + gain if kind == "rmsnorm_zc" else gain,
                       eps=eps).astype(x.dtype)
    return _layernorm(eps)(x, weight, bias)


def _head_norm(x, weight, kind: str, eps: float):
    """RMSNorm of x [..., H, Dh] per head over its Dh, gain [Dh] shared by the
    heads (zero-centred for ``kind`` "rmsnorm_zc"), in float32."""
    import jax.numpy as jnp

    from ..ops.rmsnorm import rmsnorm_reference

    gain = weight.astype(jnp.float32)
    return rmsnorm_reference(x, 1.0 + gain if kind == "rmsnorm_zc" else gain, eps)


def _join_rows(rows, join) -> dict:
    """One dict of several layers' stats, each key joined over the rows that
    have it: the layers of a period need not hand out the same (a routed
    layer's counters; what a "dsa" or a "kda" mixer found)."""
    return {key: join([row[key] for row in rows if key in row])
            for key in sorted(set().union(*rows))}


def _no_routing_stats(n_experts: int, weights: bool = False, share: bool = False) -> dict:
    """What a layer that routes nothing reports (a dense layer among routed
    ones), shaped as a routed layer's stats (``weights``: of a router with a
    selection bias, which also reports ``expert_weight``; ``share``: of a
    rank's share of the experts, which also reports ``visited_rows``)."""
    import jax.numpy as jnp

    out = {"expert_tokens": jnp.zeros((n_experts,), jnp.int32),
           "router_prob": jnp.zeros((n_experts,), jnp.float32),
           "held_rows": jnp.zeros((), jnp.int32),
           "overflow_rows": jnp.zeros((), jnp.int32)}
    if weights:
        out["expert_weight"] = jnp.zeros((n_experts,), jnp.float32)
    if share:
        out["visited_rows"] = jnp.zeros((), jnp.int32)
    return out


def yarn_inv_freq(head_dim: int, theta: float, factor: float, original_max: float,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's inverse frequencies [head_dim / 2] (numpy float32), as
    transformers' ``_compute_yarn_parameters`` (truncate: true) gives them: a
    frequency that turns more than ``beta_fast`` times over the original
    context is kept (extrapolation), one that turns fewer than ``beta_slow``
    times is divided by ``factor`` (interpolation), a linear ramp over the
    pair index between."""
    import numpy as np

    def correction_dim(rotations):
        return (head_dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    pos = theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    # ramp 0: extrapolate (the plain frequency), 1: interpolate (/ factor)
    return ((1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)).astype(np.float32)


def rope_table(seq_len: int, head_dim: int, theta: float, yarn=()):
    """(cos, sin) [seq_len, head_dim / 2] of ``head_dim`` rotated dims.
    ``yarn``: ``TransformerConfig.rope_yarn`` (factor, original context,
    beta_fast, beta_slow, attention_factor); the attention factor multiplies
    cos and sin, so q and k each carry it. () = the plain table."""
    import jax.numpy as jnp

    if yarn:
        freqs = jnp.asarray(yarn_inv_freq(head_dim, theta, *yarn[:4]))
    else:
        freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    angles = jnp.outer(t, freqs)  # [T, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return (cos * yarn[4], sin * yarn[4]) if yarn else (cos, sin)


def mrope_table(positions, head_dim: int, theta: float, sections=()):
    """(cos, sin) [B, T, head_dim / 2] from ``positions`` [streams, B, T]
    (M-RoPE; ``TransformerConfig.mrope_section``): pair j turns by
    ``theta ** (-2j / head_dim)`` times the position stream its section names
    (``sections`` [16, 24, 24]: pairs 0-15 stream 0, 16-39 stream 1, 40-63
    stream 2; () = stream 0 for every pair). Each angle is ``rope_table``'s
    product of one float32 position and one frequency, so equal streams give
    that table's values exactly."""
    import jax.numpy as jnp
    import numpy as np

    half = head_dim // 2
    if sections and sum(sections) != half:
        raise ValueError(f"mrope_section {tuple(sections)} does not add up to the "
                         f"{half} rotated pairs of a head of {head_dim}")
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.asarray(positions).astype(jnp.float32)
    stream = np.repeat(np.arange(len(sections)), sections) if sections else np.zeros(half, int)
    if pos.shape[0] <= int(stream.max()):
        raise ValueError(f"position_ids of {pos.shape[0]} stream(s) for mrope_section "
                         f"{tuple(sections)}")
    angles = pos[0][..., None] * freqs
    for i in range(1, int(stream.max()) + 1):
        angles = jnp.where(jnp.asarray(stream == i), pos[i][..., None] * freqs, angles)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin, interleaved: bool = False):
    """x: [B, T, H, D]. Rotates the first ``2 * cos.shape[-1]`` dims (partial
    rotary — GPT-NeoX rotary_pct / GPT-J rotary_dim); the rest pass through.
    ``cos`` / ``sin`` [T, rd / 2], or [B, T, rd / 2] (a table a sequence:
    ``mrope_table``).

    interleaved=False: llama/NeoX rotate-half pairing (dim i with i + rd/2).
    interleaved=True:  GPT-J rotate-every-two pairing (dim 2i with 2i+1).
    """
    import jax.numpy as jnp

    rd = 2 * cos.shape[-1]
    rot, rest = (x[..., :rd], x[..., rd:]) if rd < x.shape[-1] else (x, None)
    if cos.ndim == 3:
        c, s = cos[:, :, None, :].astype(x.dtype), sin[:, :, None, :].astype(x.dtype)
    else:
        c = cos[None, :, None, :].astype(x.dtype)
        s = sin[None, :, None, :].astype(x.dtype)
    if interleaved:
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        out = out.reshape(rot.shape)
    else:
        x1, x2 = jnp.split(rot, 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out if rest is None else jnp.concatenate([out, rest], axis=-1)


def alibi_slopes(n_heads: int):
    """BLOOM/ALiBi head slopes (press et al.; matches HF build_alibi_tensor)."""
    import numpy as np

    def pow2(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(n_heads).is_integer():
        s = pow2(n_heads)
    else:
        m = 2 ** math.floor(math.log2(n_heads))
        s = pow2(m) + pow2(2 * m)[0::2][: n_heads - m]
    return np.asarray(s, np.float32)


def decode_fusion_eligibility(cfg: "TransformerConfig",
                              speculative_k: int = 0) -> dict:
    """Which parts of the fused Pallas decode path (ops/fused_decode.py)
    this model STRUCTURE supports — the single source of truth both
    serving engines consult when ``decode_kernel`` resolves to "pallas".

    Returns ``{"qkv": None | reason, "mlp": None | reason,
    "verify": None | reason}``; ``None`` means fusable. Per-layer
    WEIGHT-form checks (dense vs QuantizedMatrix, group sizes) happen at
    dispatch time in the engines — this classifies only what is knowable
    from the config. Attention fusion has no structural requirements
    beyond the engine-wide pre-LN layer body (GQA H % KV == 0 is a
    construction invariant).

    ``speculative_k`` (ISSUE 8 satellite): the serving config's draft
    width. The fused decode kernels — QKV+RoPE+pool-append and the split-K
    flash-decode — are SINGLE-token by construction (one row, one new KV
    slot, ``kv_len = pos + 1``); a speculative verify row is ``k+1``
    tokens wide and silently routing it through them would read a stale
    kv_len and drop k appends. The ``"verify"`` entry makes that gate
    explicit: with ``speculative_k > 0`` the verify rows must take the
    paged-EXTEND path (the chunked-prefill kernel, which is multi-token
    by construction), and only plain 1-token decode rows stay fused.

    One-dispatch sampling (ISSUE 16) does not change this
    classification: the fused sampler
    (``inference/sampling.py::seeded_tokens``) composes AFTER the layer
    stack, on the gathered final-position logits, inside the same
    compiled program — so every sampling mode (greedy, temperature/
    top-k/top-p, logit-masked, EOS early-stop) keeps whatever fused
    decode path the structure earns here. The only sampling-adjacent
    routing change is the one speculation already imposes: sampled
    verify rows are still ``k+1`` tokens wide and still take the
    paged-extend route per the ``"verify"`` entry.
    """
    from ..ops.fused_decode import FUSABLE_ACTIVATIONS

    qkv = None
    if cfg.position == "rope" and cfg.rope_interleaved:
        qkv = ("interleaved (GPT-J rotate-every-two) rope pairing: the "
               "fused kernel's lane-roll rotate-half form does not cover it")
    mlp = None
    if cfg.n_experts > 0:
        mlp = ("MoE FFN (expert dispatch stays on the moe_layer path, "
               "which itself admits int8/fp8 streamed expert weights — "
               "the grouped-GEMM/einsum dequant fuses into the dot)")
    elif cfg.activation not in FUSABLE_ACTIVATIONS:
        mlp = (f"activation {cfg.activation!r} has no Mosaic lowering "
               f"(fusable: {', '.join(FUSABLE_ACTIVATIONS)})")
    elif cfg.norm not in ("rmsnorm", "layernorm"):
        mlp = f"unknown norm {cfg.norm!r}"
    verify = None
    if speculative_k > 0:
        verify = (
            f"speculative verify rows are {speculative_k + 1} tokens wide; "
            "the fused decode kernels are single-token (one append, "
            "kv_len = pos + 1) — verify rows route through the "
            "paged-extend kernel; fused decode applies to plain decode "
            "rows only")
    return {"qkv": qkv, "mlp": mlp, "verify": verify}


def causal_attention(q, k, v, attention_impl: str = "auto", alibi=None,
                     causal: bool = True, window: int = 0):
    """q: [B,T,H,D], k/v: [B,T,Hkv,D] → [B,T,H,D]. fp32 softmax.

    Dispatches to the Pallas flash kernel on TPU (ops/flash_attention);
    jnp reference elsewhere. ``alibi`` = per-head slopes [H] (BLOOM).
    ``causal=False`` = bidirectional (encoder models). ``window`` > 0: a
    query sees itself and the ``window - 1`` keys before it (mixer "swa")."""
    from ..ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=causal, impl=attention_impl,
                           alibi_slopes=alibi, window=window)


def _windowed_attention(q, k, v, window: int, local_flag):
    """Causal attention with a conditional trailing window (GPT-Neo local
    layers, reference containers/gptneo.py). ``local_flag`` is a traced
    bool — True restricts key j to i - j < window — so global and local
    layers share one scanned program. A FLAG of one kind of layer over a
    dense [T, T] mask that no kernel reads: GPT-Neo's form (``hf.py``'s
    import of it) and short contexts only. A model whose window layers are a
    kind of their own (their own shapes, long sequences) states mixer "swa"
    in its ``layer_pattern`` and goes through the kernels
    (``Transformer._gqa``, ``ops/flash_attention`` ``window``)."""
    import jax
    import jax.numpy as jnp

    from ..ops.flash_attention import _repeat_kv

    n_rep = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    T = q.shape[1]
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    mask = mask & jnp.where(local_flag, (i - j) < window, True)
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


class Transformer:
    """Functional model: ``init(rng) -> params``, ``apply(params, ids) ->
    logits``, ``loss(params, batch, rng) -> scalar`` (next-token CE)."""

    def __init__(self, config: TransformerConfig):
        self.config = config
        if config.norm_order not in ("input", "output", "sandwich"):
            raise ValueError("norm_order is 'input', 'output' or 'sandwich'; got "
                             f"{config.norm_order!r}")
        if config.loop_steps < 1 or (config.exit_gate and config.loop_steps == 1):
            raise ValueError(f"loop_steps is >= 1 (got {config.loop_steps}) and an "
                             "exit_gate is a looped stack's (loop_steps > 1)")
        if config.loop_steps > 1 and (config.post_ln or config.mlm_head or config.n_experts > 0
                                      or config.recurrent):
            raise NotImplementedError(
                "a looped stack (loop_steps > 1) runs the final norm inside the loop "
                "and counts nothing a visit: a post_ln / mlm_head encoder has no "
                "final norm, routed experts' counters have a row a layer, not a row "
                "a (loop step, layer), and the state-space / DeltaNet scans' chunk "
                "counters count one pass")
        if config.moe_router_input not in ("ffn", "block"):
            raise ValueError("moe_router_input is 'ffn' or 'block'; got "
                             f"{config.moe_router_input!r}")
        forms = [name for name, on in (("post_ln", config.post_ln),
                                       ("parallel_block", config.parallel_block),
                                       (f"norm_order={config.norm_order!r}",
                                        config.norm_order != "input"))
                 if on]
        if len(forms) > 1:
            raise ValueError(
                "a block norms its sublayers' input (in sequence, or in parallel: "
                "parallel_block), their output (norm_order 'output'), both (norm_order "
                "'sandwich') or the sum (post_ln), one of them; this configuration "
                f"sets {' and '.join(forms)}")

    # -- parameters ----------------------------------------------------

    def init(self, rng) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        cfg = self.config
        L, D, H, KV, Dh, F = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.ff_dim
        keys = iter(jax.random.split(rng, 16))

        params: Dict[str, Any] = {
            "embed": jax.random.normal(next(keys), (cfg.vocab_size, D), jnp.float32) * 0.02,
        }
        if cfg.position == "learned":
            # +pos_offset rows so OPT-style offset indexing stays in bounds
            # right up to T == max_seq_len (checkpoints for such archs store
            # the offset rows the same way).
            params["pos_embed"] = jax.random.normal(
                next(keys), (cfg.max_seq_len + cfg.pos_offset, D), jnp.float32) * 0.02
        pattern = cfg.pattern
        if cfg.lead_layers:
            # the leading layers, stacked on their own [lead_layers, ...], from
            # a key of their own: the draws of the layers that follow do not
            # move with them
            if not 0 < cfg.lead_layers < L or len(cfg.lead_kind) != 2:
                raise ValueError(f"lead_layers {cfg.lead_layers} of kind "
                                 f"{cfg.lead_kind!r} in a stack of {L} layers")
            params["lead"] = self._init_kind(
                iter(jax.random.split(jax.random.fold_in(rng, 0x1EAD), 16)),
                tuple(cfg.lead_kind), (cfg.lead_layers,))
            L = L - cfg.lead_layers
        if len(pattern) == 1:
            # one kind: every leaf stacked [L, ...]
            params["layers"] = self._init_kind(keys, pattern[0], (L,))
        else:
            if L % len(pattern):
                raise ValueError(f"n_layers {L} is not whole periods of the "
                                 f"{len(pattern)}-layer pattern")
            # each kind stacked on its own: [periods, layers of it a period, ...]
            periods = L // len(pattern)
            params["layers"] = {
                name: self._init_kind(
                    iter(jax.random.split(jax.random.fold_in(next(keys), j), 16)),
                    kind, (periods, sum(1 for k in pattern if k == kind)))
                for j, (name, kind) in enumerate(self.kinds().items())}
        if cfg.type_vocab_size > 0:
            params["token_type_embed"] = jax.random.normal(
                next(keys), (cfg.type_vocab_size, D), jnp.float32) * 0.02
        if cfg.embed_ln:
            params["embed_ln_w"], params["embed_ln_b"] = jnp.ones((D,)), jnp.zeros((D,))
        if not cfg.post_ln:
            # post-LN encoders (BERT) normalize inside each block and have
            # no final norm before the head
            # a zero-centred gain (x * (1 + w)) starts at 0
            params["ln_f_w"] = (jnp.zeros if cfg.norm == "rmsnorm_zc" else jnp.ones)((D,))
            params["ln_f_b"] = jnp.zeros((D,))
        if cfg.mlm_head:
            params["mlm_dense_w"] = jax.random.normal(next(keys), (D, D), jnp.float32) / math.sqrt(D)
            params["mlm_dense_b"] = jnp.zeros((D,))
            params["mlm_ln_w"], params["mlm_ln_b"] = jnp.ones((D,)), jnp.zeros((D,))
            params["mlm_bias"] = jnp.zeros((cfg.vocab_size,))
        if not cfg.tie_embeddings:
            params["unembed"] = jax.random.normal(next(keys), (D, cfg.vocab_size), jnp.float32) * 0.02
            if cfg.unembed_bias:
                params["unembed_b"] = jnp.zeros((cfg.vocab_size,))
        if cfg.exit_gate:
            # from a key of its own: the other leaves' draws do not move with it
            params["exit_gate_w"] = jax.random.normal(
                jax.random.fold_in(rng, 0xE817), (D,), jnp.float32) * 0.02
            params["exit_gate_b"] = jnp.zeros(())
        return params

    def kinds(self) -> Dict[str, Tuple[str, str]]:
        """{name under ``params["layers"]``: (mixer, ffn)} for each distinct
        kind of the pattern, in the order it first appears."""
        return {name: kind for name, _, kind in self.slots()}

    def slots(self):
        """The period, slot by slot: [(kind's name, index among the layers of
        that kind in a period, (mixer, ffn))]."""
        seen: Dict[str, int] = {}
        out = []
        for mixer, ffn in self.config.pattern:
            name = f"{mixer}_{ffn}"
            out.append((name, seen.get(name, 0), (mixer, ffn)))
            seen[name] = seen.get(name, 0) + 1
        return out

    def _init_kind(self, keys, kind, lead):
        """One kind's parameters, every leaf with the leading shape ``lead``
        ((n_layers,) for a one-kind model)."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        mixer, ffn = kind
        L, D, H, KV, Dh, F = (cfg.n_layers, cfg.d_model, cfg.heads_of(mixer),
                              cfg.kv_heads, cfg.head_dim, cfg.ff_dim)
        n = math.prod(lead)

        def stack(key, shape, fan_in, scale=1.0):
            return jax.random.normal(key, lead + shape, jnp.float32) * (scale / math.sqrt(fan_in))

        ones = lambda *shape: jnp.ones(lead + shape)
        zeros = lambda *shape: jnp.zeros(lead + shape)
        # a zero-centred gain (x * (1 + w)) starts at 0
        gain = zeros if cfg.norm == "rmsnorm_zc" else ones
        biased_norm = cfg.norm != "rmsnorm_zc"
        layer = {"ln1_w": gain(D)}
        if biased_norm:
            layer["ln1_b"] = zeros(D)
        sandwich = cfg.norm_order == "sandwich"
        if sandwich:
            # the way out of the mixer half (a plain gain, no unused bias leaf)
            layer["ln1_post_w"] = gain(D)
            if cfg.norm == "layernorm":
                layer["ln1_post_b"] = zeros(D)
        if mixer == "gdn":
            Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
            dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
            conv_dim = 2 * Hk * dk + Hv * dv
            layer.update({
                # grouped per key head as the source groups them:
                # [q dk | k dk | v (Hv/Hk) dv | z (Hv/Hk) dv] and [b | a]
                "w_qkvz": stack(next(keys), (D, 2 * Hk * dk + 2 * Hv * dv), D),
                "w_ba": stack(next(keys), (D, 2 * Hv), D),
                "conv_w": stack(next(keys), (cfg.gdn_conv_kernel, conv_dim),
                                cfg.gdn_conv_kernel),
                # the source's own: A = U(0, 16), dt_bias = 1, a plain gain
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), lead + (Hv,), jnp.float32, 1e-3, 16.0)),
                "dt_bias": ones(Hv),
                "gdn_norm_w": ones(dv),
                "w_out": stack(next(keys), (Hv * dv, D), Hv * dv,
                               scale=1.0 / math.sqrt(2 * L)),
            })
        elif mixer == "kda":
            Hk, dk, dv, r = (cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim,
                             cfg.kda_gate_rank)
            if min(Hk, dk, dv, r) <= 0:
                raise ValueError("mixer 'kda' needs kda_heads, kda_key_dim, "
                                 "kda_value_dim and kda_gate_rank > 0")
            ka, kd = jax.random.split(next(keys))
            # the published modelling code's draws (fla's KimiDeltaAttention):
            # A = U[1, 16] a head, the decay's bias the inverse softplus of a
            # log-uniform step in [0.001, 0.1] a channel floored at 1e-4
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                kd, lead + (Hk * dk,), jnp.float32, math.log(1e-3), math.log(1e-1))), 1e-4)
            layer.update({
                # [q Hk dk | k Hk dk | v Hk dv], the checkpoint's three
                # projections side by side, and the taps over them in that order
                "kda_w_qkv": stack(next(keys), (D, 2 * Hk * dk + Hk * dv), D),
                "kda_conv_w": stack(next(keys), (cfg.kda_conv_kernel, 2 * Hk * dk + Hk * dv),
                                    cfg.kda_conv_kernel),
                "kda_w_beta": stack(next(keys), (D, Hk), D),
                # the decay's low-rank pair, and the output gate's
                "kda_w_fa": stack(next(keys), (D, r), D),
                "kda_w_fb": stack(next(keys), (r, Hk * dk), r),
                "kda_w_ga": stack(next(keys), (D, r), D),
                "kda_w_gb": stack(next(keys), (r, Hk * dv), r),
                "kda_A_log": jnp.log(jax.random.uniform(ka, lead + (Hk,), jnp.float32, 1.0, 16.0)),
                "kda_dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "kda_norm_w": ones(dv),
                "kda_w_out": stack(next(keys), (Hk * dv, D), Hk * dv,
                                   scale=1.0 / math.sqrt(2 * L)),
            })
        elif mixer == "sconv":
            layer.update({
                # three blocks of D columns: [gate before B | gate after C | x]
                "sconv_w_in": stack(next(keys), (D, 3 * D), D),
                # the taps [K, D]: tap j weighs position t - (K - 1) + j
                "sconv_w": stack(next(keys), (cfg.sconv_taps, D), cfg.sconv_taps),
                "sconv_w_out": stack(next(keys), (D, D), D, scale=1.0 / math.sqrt(2 * L)),
            })
        elif mixer == "ssm":
            Hs, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
            inner, conv = Hs * P, Hs * P + 2 * G * N
            ka, kd, kb = jax.random.split(next(keys), 3)
            # the family's own draws: A = U[1, 16], the step's bias the inverse
            # softplus of a log-uniform step in [0.001, 0.1] floored at 1e-4
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                kd, lead + (Hs,), jnp.float32, math.log(1e-3), math.log(1e-1))), 1e-4)
            layer.update({
                # [z inner | x inner | B G N | C G N | dt heads]
                "ssm_w_in": stack(next(keys), (D, inner + conv + Hs), D),
                # the taps [K, conv]: tap j weighs position t - (K - 1) + j
                "ssm_conv_w": stack(next(keys), (cfg.ssm_conv_kernel, conv),
                                    cfg.ssm_conv_kernel),
                # torch's Conv1d default: U(+-1/sqrt(taps)); at 0 a model
                # without the bias computes the same function
                "ssm_conv_b": jax.random.uniform(
                    kb, lead + (conv,), jnp.float32, -1.0, 1.0) / math.sqrt(cfg.ssm_conv_kernel),
                "ssm_A_log": jnp.log(jax.random.uniform(ka, lead + (Hs,), jnp.float32, 1.0, 16.0)),
                "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "ssm_D": ones(Hs),
                "ssm_norm_w": ones(inner),
                "ssm_w_out": stack(next(keys), (inner, D), inner, scale=1.0 / math.sqrt(2 * L)),
            })
        elif mixer == "mla":
            r, dc, dr, dv = (cfg.mla_kv_rank, cfg.mla_qk_content_dim,
                             cfg.mla_qk_rope_dim, cfg.mla_v_dim)
            layer.update({
                # per head [content dc | rope dr]
                "mla_wq": stack(next(keys), (D, H * (dc + dr)), D),
                # [latent r | the ONE rotary key dr]
                "mla_wkv_a": stack(next(keys), (D, r + dr), D),
                "mla_kv_norm_w": ones(r),
                # per head [content key dc | value dv]
                "mla_wkv_b": stack(next(keys), (r, H * (dc + dv)), r),
                "mla_wo": stack(next(keys), (H * dv, D), H * dv,
                                scale=1.0 / math.sqrt(2 * L)),
            })
        else:
            gated = mixer == "gated_attn"
            layer.update({
                # gated: per head [q Dh | gate Dh]
                "wq": stack(next(keys), (D, H * Dh * (2 if gated else 1)), D),
                "wk": stack(next(keys), (D, KV * Dh), D),
                "wv": stack(next(keys), (D, KV * Dh), D),
                "wo": stack(next(keys), (H * Dh, D), H * Dh, scale=1.0 / math.sqrt(2 * L)),
            })
            if cfg.attn_qkv_bias:
                layer["b_q"] = zeros(H * Dh)
                layer["b_k"] = zeros(KV * Dh)
                layer["b_v"] = zeros(KV * Dh)
            if cfg.attn_out_bias:
                layer["b_o"] = zeros(D)
            if gated:                          # per head, over its Dh
                layer["q_norm_w"], layer["k_norm_w"] = gain(Dh), gain(Dh)
            elif cfg.qk_norm == "head":        # per head too, a plain gain
                if mixer not in ("attn", "dsa"):
                    raise ValueError(f"qk_norm='head' is mixer 'attn''s and 'dsa''s; {mixer!r} "
                                     "has no q/k norm")
                layer["q_norm_w"], layer["k_norm_w"] = ones(Dh), ones(Dh)
            elif cfg.qk_norm:
                layer["q_norm_w"] = ones(H * Dh)
                layer["k_norm_w"] = ones(KV * Dh)
            if mixer == "dsa":
                # the indexer: its heads' queries, ONE key head with a
                # LayerNorm of its own, a weight a head and token
                Hi, Di = cfg.dsa_index_heads, cfg.dsa_index_dim
                if min(Hi, Di, cfg.dsa_topk) <= 0:
                    raise ValueError("mixer 'dsa' needs dsa_index_heads, dsa_index_dim "
                                     "and dsa_topk > 0")
                layer.update({
                    "dsa_wq": stack(next(keys), (D, Hi * Di), D),
                    "dsa_wk": stack(next(keys), (D, Di), D),
                    "dsa_ww": stack(next(keys), (D, Hi), D),
                    "dsa_k_norm_w": ones(Di), "dsa_k_norm_b": zeros(Di)})
        if ffn == "none":
            # a mixer alone: no second norm, no ffn leaves
            return layer
        if ffn not in ("mlp", "moe"):
            raise ValueError(f"a layer's ffn is 'mlp', 'moe' or 'none'; got {ffn!r}")
        if not (cfg.parallel_block and cfg.parallel_shared_ln):
            layer["ln2_w"] = gain(D)
            if biased_norm:
                layer["ln2_b"] = zeros(D)
        if sandwich:
            layer["ln2_post_w"] = gain(D)
            if cfg.norm == "layernorm":
                layer["ln2_post_b"] = zeros(D)
        if ffn == "moe":
            import jax.random as jrandom

            from ..moe.layer import init_expert_mlp

            ek = next(keys)
            per_layer = [init_expert_mlp(k, cfg.experts_held, D, F, cfg.activation)
                         for k in jrandom.split(ek, n)]
            layer["moe_gate"] = stack(next(keys), (D, cfg.n_experts), D)
            if cfg.moe_select_bias:
                # the aux-free balancing buffer: selects, is not weighed, gets
                # no gradient (gating.topk_select); 0 = no bias
                layer["moe_select_bias"] = zeros(cfg.n_experts)
            for name in per_layer[0]:
                held = jnp.stack([p[name] for p in per_layer])
                layer[f"moe_{name}"] = held.reshape(lead + held.shape[1:])
            if cfg.moe_shared_expert_ff > 0:
                Fs = cfg.moe_shared_expert_ff
                if gate_fn(cfg.activation):        # an ungated one has no gate matrix
                    layer["moe_shared_w_gate"] = stack(next(keys), (D, Fs), D)
                layer["moe_shared_w_up"] = stack(next(keys), (D, Fs), D)
                layer["moe_shared_w_down"] = stack(next(keys), (Fs, D), Fs)
                if cfg.moe_shared_gate == "sigmoid":
                    layer["moe_shared_gate"] = zeros(D, 1)
                elif cfg.moe_shared_gate != "none":
                    raise ValueError("moe_shared_gate must be 'sigmoid' or 'none'; "
                                     f"got {cfg.moe_shared_gate!r}")
        elif gate_fn(cfg.activation):
            F = cfg.dense_ff_dim
            layer["w_gate"] = stack(next(keys), (D, F), D)
            layer["w_up"] = stack(next(keys), (D, F), D)
            layer["w_down"] = stack(next(keys), (F, D), F, scale=1.0 / math.sqrt(2 * L))
        else:
            F = cfg.dense_ff_dim
            layer["w_up"] = stack(next(keys), (D, F), D)
            layer["w_down"] = stack(next(keys), (F, D), F, scale=1.0 / math.sqrt(2 * L))
            if cfg.mlp_bias:
                layer["b_up"] = zeros(F)
                layer["b_down"] = zeros(D)
        return layer

    # -- partition specs (AutoTP analog) -------------------------------

    def partition_specs(self, params) -> Dict[str, Any]:
        import jax
        from jax.sharding import PartitionSpec as P

        cfg = self.config

        def spec_for(path: Tuple[str, ...], leaf):
            name = path[-1]
            # a one-kind stack leads with [layers], a kind of a pattern with
            # [periods, layers of the kind a period]
            lead = (None,) * (len(path) - 1) if path[0] in ("layers", "lead") else ()
            if name.startswith("moe_shared"):
                # shared expert = a dense MLP: column/row parallel like w_*
                if name in ("moe_shared_w_gate", "moe_shared_w_up"):
                    return P(*lead, None, "tensor")
                if name == "moe_shared_w_down":
                    return P(*lead, "tensor", None)
                return P(*lead, None, None)      # the scalar gate
            if name == "moe_select_bias":
                return P(*lead, None)
            if name.startswith("moe_") and name != "moe_gate":
                # single source of truth for expert sharding lives in moe/layer.py
                from ..moe.layer import expert_partition_specs

                base = expert_partition_specs({name[4:]: None})[name[4:]]
                return P(*lead, *base)
            if name == "moe_gate":
                return P(*lead, None, None)
            if name.startswith("kda_"):
                # nor is the KDA mixer: one leaf holds its three projections
                # side by side, and the taps keep that order
                return P(*((None,) * leaf.ndim))
            if name.startswith("ssm_"):
                # the state-space mixer is not split over "tensor": its input
                # projection's blocks and the taps over them keep the
                # checkpoint's channel order, which no even split cuts whole
                return P(*((None,) * leaf.ndim))
            if name in ("wq", "wk", "wv", "w_gate", "w_up", "w_qkvz", "w_ba",
                        "mla_wq", "mla_wkv_b"):
                return P(*lead, None, "tensor")       # column parallel
            if name in ("wo", "w_down", "w_out", "mla_wo"):
                return P(*lead, "tensor", None)       # row parallel
            if name in ("b_up", "b_q", "b_k", "b_v") or (
                    name in ("q_norm_w", "k_norm_w") and cfg.qk_norm is True):
                return P(*lead, "tensor")  # column-parallel biases and gains
            if name.startswith("sconv_"):
                # the convolution mixer is not split over "tensor": its input
                # projection's three blocks would each need their own columns
                return P(*((None,) * leaf.ndim))
            if name == "embed":
                return P("tensor", None)              # vocab parallel
            if name == "unembed":
                return P(None, "tensor")
            return P(*((None,) * leaf.ndim))

        flat = {}
        def walk(tree, path):
            if isinstance(tree, dict):
                return {k: walk(v, path + (k,)) for k, v in tree.items()}
            return spec_for(path, tree)

        return walk(params, ())

    # -- forward pieces (shared by the plain and pipelined paths) ------

    def embed(self, params, input_ids, position_ids=None):
        """ids [.., T] -> (x [.., T, D], rope (cos, sin) or (None, None)).
        ``position_ids`` [streams, B, T] (M-RoPE: ``mrope_section``; None =
        every stream 0..T-1, text). A model with a learned sparse attention
        (mixer "dsa") gets (cos, sin, the indexer's cos, sin): ``rope_for``."""
        with trace.scope("embed"):
            return self._embed(params, input_ids, position_ids)

    def _embed(self, params, input_ids, position_ids=None):
        import jax.numpy as jnp

        cfg = self.config
        T = input_ids.shape[-1]
        x = jnp.take(params["embed"], input_ids, axis=0)
        if cfg.embed_scale != 1.0:
            x = (cfg.embed_scale * x.astype(jnp.float32)).astype(x.dtype)
        if cfg.position == "learned":
            x = x + params["pos_embed"][cfg.pos_offset:cfg.pos_offset + T].astype(x.dtype)
        if cfg.type_vocab_size > 0:
            # token_type row 0 (the HF default when token_type_ids is None)
            x = x + params["token_type_embed"][0].astype(x.dtype)
        if cfg.embed_ln:
            # BLOOM word_embeddings_layernorm; BERT embeddings.LayerNorm
            # (after the word+pos+type sum — BLOOM has no learned pos, so
            # the shared placement is exact for both)
            x = _norm(x, params["embed_ln_w"], params["embed_ln_b"], cfg.norm,
                      eps=cfg.norm_eps)
        if cfg.position in ("learned", "alibi", "none"):
            return x, (None, None)
        if position_ids is not None and not cfg.mrope_section:
            raise NotImplementedError("position_ids are M-RoPE's (mrope_section); this "
                                      "model rotates by the position in the sequence")
        sparse = any(mixer == "dsa" for mixer, _ in cfg.kinds_used)
        return x, self.rope_for("dsa" if sparse else "attn", T, position_ids)

    def rope_for(self, mixer: str, seq_len: int, position_ids=None):
        """The (cos, sin) table the layers of mixer ``mixer`` rotate by: the
        model's own (``rope_theta``, ``rotary_dim``, ``rope_yarn``) or the
        window kind's (``swa_rope_theta``, ``swa_rotary_dim``, unscaled);
        (None, None) for a kind that rotates nothing (``unrotated_mixers``).
        With ``mrope_section`` the model's own table is M-RoPE's, [B, T, .],
        from ``position_ids`` [3, B, T] (None: every stream 0..T-1, under the
        scope ``mrope``). Mixer "dsa": (cos, sin, the indexer's cos, sin), the
        indexer's over its whole ``dsa_index_dim`` by the temporal stream."""
        cfg = self.config
        if mixer == "dsa" or (mixer == "attn" and cfg.mrope_section):
            import jax.numpy as jnp

            with trace.scope("mrope"):
                if position_ids is None:
                    position_ids = jnp.broadcast_to(
                        jnp.arange(seq_len, dtype=jnp.int32),
                        (max(1, len(cfg.mrope_section)), 1, seq_len))
                own = mrope_table(position_ids, cfg.rotary_dims, cfg.rope_theta,
                                  cfg.mrope_section)
                if mixer != "dsa":
                    return own
                return own + mrope_table(position_ids[:1], cfg.dsa_index_dim,
                                         cfg.rope_theta)
        if mixer in cfg.unrotated_mixers:
            return None, None
        if mixer == "swa":
            return rope_table(seq_len, cfg.swa_rotary_dim or cfg.head_dim,
                              cfg.swa_rope_theta or cfg.rope_theta)
        return rope_table(seq_len, cfg.rotary_dims, cfg.rope_theta, cfg.rope_yarn)

    def layer_apply(self, lw, h, rope, local=None, moe_on=None, kind=None,
                    remat_halves=False):
        """One transformer block. h [B, T, D] -> (h, (moe_aux, this layer's
        router stats or None)): a carry and an output, as ``lax.scan`` wants.
        ``kind``: the layer's (mixer, ffn) of ``cfg.pattern``; None = the
        first (a one-kind model's only one). Every kind runs this one
        skeleton, whatever the stack's other layers are: a mixer half and a
        feed-forward half, each ``h + f(h)`` with the block's norm where
        ``place`` says.

        ``local`` (traced bool scalar, GPT-Neo): this layer restricts
        attention to the trailing ``local_attention_window`` positions.
        ``moe_on`` (traced bool scalar, Megatron --expert-interval): False
        routes this layer through the dense FFN stored in expert slot 0
        (the flag is replica-identical, so both lax.cond branches keep a
        uniform collective schedule across devices).

        Under remat the two halves are checkpointed EACH (``remat_halves``,
        set by stack_apply): the backward then holds the mixer's residuals or
        the FFN's, never both, for the same recomputation as one checkpoint a
        layer (Qwen3-Next at 16,384 tokens: 1.5 GB of a 16 GB chip, PR 33).
        What a layer keeps between the passes: each half's input (B x T x D)
        and, where the mixer's attention takes a splash route, the kernel's
        own ``out`` and ``logsumexp`` (B x H x T x (Dv + 2) x 2 bytes in
        bf16: 136 MB at kanana-2's 2 x 32 x 8192 x 128), the one result of the
        half that its replay would only rebuild: the backward kernels start
        from them and the forward kernel runs once a layer and step, not twice
        (PR 36). That is under every policy, "full" too, which therefore keeps
        twice what it kept for these mixers (kanana-2 at 48 layers and 16,384
        tokens a chip: 13.0 GB where it kept 6.4); policy "none"
        (``jax.checkpoint``'s default) keeps nothing and recomputes. ``gdn``
        has no such kernel; MHA takes "splash" on one device (PR 56) and keeps
        them like any other; "stock_flash" (MHA per shard of a kernel mesh),
        "reference" and the ring's hop kernels name nothing and recompute."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        mixer, ffn = kind or cfg.pattern[0]
        mix = {"gdn": self._gdn, "kda": self._kda, "gated_attn": self._gated_attention,
               "mla": self._mla, "sconv": self._sconv, "ssm": self._ssm,
               "attn": functools.partial(self._gqa, local=local),
               "swa": functools.partial(self._gqa, mixer="swa"),
               "dsa": self._dsa}[mixer]
        # where the block norms (``Transformer.__init__`` admits one form):
        # each sublayer's input (pre-LN), its output (the Olmo 2 / 3 order),
        # both (Ouro's sandwich: the input's gain and a second, ``ln*_post_w``),
        # the sum (BERT's post-LN), or the input of a parallel block (GPT-J /
        # NeoX / Falcon: h + mix(ln1 h) + ffn(ln2 h, or ln1 h again)
        place = ("parallel" if cfg.parallel_block else "sum" if cfg.post_ln
                 else cfg.norm_order)
        # what the feed-forward half reads of the block's first half, where it
        # reads anything. x, the block's input as the mixer half got it: a
        # router that reads the block's INPUT (``moe_router_input`` "block")
        # and a parallel block's second norm (under per-half remat x is the
        # mixer half's kept input already, so a layer keeps nothing more
        # between the passes; the routing is replayed with the half, as it is
        # for a router that reads y2, and the router's gradient reaches x).
        # y2, the mixer half's normed input: a parallel block of ONE norm
        block_router = cfg.moe_router_input == "block" and ffn == "moe"
        shared = place == "parallel" and cfg.parallel_shared_ln and ffn != "none"

        def normed(lw, x, n, scope, post=""):
            with trace.scope(scope):
                return _norm(x, lw[f"ln{n}{post}_w"], lw.get(f"ln{n}{post}_b", 0), cfg.norm,
                             eps=cfg.norm_eps)

        def add(h, out, scope):
            """``h + residual_scale * out``: a scaled sum is formed in float32
            and rounded once, under the scope of the sublayer whose output it
            scales."""
            if cfg.residual_scale == 1.0:
                return h + out
            with trace.scope(scope):
                return (h.astype(jnp.float32) + cfg.residual_scale
                        * out.astype(jnp.float32)).astype(h.dtype)

        # a "dsa" and a "kda" mixer hand out, beside their output, what the
        # step reports of them (the selection's counters and the indexer's own
        # loss; what the rule's states keep over a chunk): ``found`` rides
        # with the layer's stats
        finds = mixer in ("dsa", "kda")
        if mixer == "dsa" and (shared or ffn != "moe"):
            raise NotImplementedError(
                "mixer 'dsa' (a learned sparse attention) is written for a "
                "sequential block with routed experts: its counters and its "
                "indexer's loss ride with the router's stats")
        if finds and shared:
            raise NotImplementedError(
                f"mixer {mixer!r} hands its statistics out of the mixer half, "
                "where a parallel block of one norm hands out its normed input")

        def mixer_half(lw, h):
            y = (normed(lw, h, 1, "attn_norm") if place in ("input", "parallel", "sandwich")
                 else h)
            out = mix(lw, y, rope)
            if finds:
                out, found = out
            if place == "output":
                out = normed(lw, out, 1, "attn_norm")
            elif place == "sandwich":
                out = normed(lw, out, 1, "attn_norm", "_post")
            h = add(h, out, "attn_out")
            if place == "sum":
                h = normed(lw, h, 1, "attn_norm")
            if finds:
                return h, found
            return (h, y) if shared else h

        def ffn_half(lw, h, x=None, y2=None):
            if place == "parallel":
                y2 = normed(lw, x, 2, "mlp_norm") if y2 is None else y2
            else:
                y2 = normed(lw, h, 2, "mlp_norm") if place in ("input", "sandwich") else h
            with trace.scope("moe" if ffn == "moe" else "mlp"):
                ff, aux, stats = self._ffn(lw, y2, moe_on, ffn,
                                           router_x=x if block_router else None)
                if place not in ("output", "sandwich"):
                    h = add(h, ff, "mlp")
            if place == "output":
                h = add(h, normed(lw, ff, 2, "mlp_norm"), "mlp")
            elif place == "sandwich":
                h = add(h, normed(lw, ff, 2, "mlp_norm", "_post"), "mlp")
            return normed(lw, h, 2, "mlp_norm") if place == "sum" else h, aux, stats

        if remat_halves:
            policy = _remat_policy(cfg.remat_policy)
            mixer_half = jax.checkpoint(
                mixer_half, policy=_keeping_splash_residuals(policy))
            ffn_half = jax.checkpoint(ffn_half, policy=policy)
        if ffn == "none":
            # a mixer alone: one residual step, nothing routed
            h, found = mixer_half(lw, h) if finds else (mixer_half(lw, h), None)
            return h, (jnp.zeros((), jnp.float32), found)
        x = h if block_router or place == "parallel" else None
        if finds:
            h, found = mixer_half(lw, h)
            h, aux, stats = ffn_half(lw, h, x, None)
            return h, (aux, {**(stats or {}), **found})
        h, y2 = mixer_half(lw, h) if shared else (mixer_half(lw, h), None)
        h, aux, stats = ffn_half(lw, h, x, y2)
        return h, (aux, stats)

    def _gqa(self, lw, y, rope, mixer="attn", local=None):
        """Softmax attention, every model's, on the block's (normed) input
        y [B, T, D] -> [B, T, D]: ``q = y Wq`` [H x Dh], ``k = y Wk``,
        ``v = y Wv`` [KV x Dh] (plus ``b_q`` / ``b_k`` / ``b_v`` with
        ``attn_qkv_bias``), RoPE by ``rope`` (the kind's own table:
        ``rope_for``), attention (causal unless ``cfg.causal`` is False: the
        encoders), ``o Wo`` (plus ``b_o`` with ``attn_out_bias``). Mixer
        "attn": the model's ``n_heads``, every earlier key visible. Mixer
        "swa": ``swa_heads`` heads and the ``swa_window`` keys up to the
        query's own, with its own scopes nested in the attention layer's
        (``swa_qkv`` and ``swa_rope`` in ``attn_qkv``, ``swa_core`` in
        ``attn_core``, ``swa_out`` in ``attn_out``); an "attn" layer that
        rotates by a YaRN table does so under ``rope_yarn``. With ``qk_norm``
        "head" q and k are normed per head over ``head_dim`` (a plain gain
        [head_dim] each, the block norm's eps) BEFORE the rotation, under
        ``attn_qk_norm`` (LFM2). With ``qk_norm`` True they are normed over
        the WHOLE projection (all heads together, gains [H x Dh] and
        [KV x Dh], a float32 statistic) before the split into heads, under
        ``attn_qk_norm`` (OLMoE, Olmo 2 / 3). With ``position`` "rope" the
        layer rotates; with "alibi" the scores carry the heads' slopes
        (``alibi_slope_scale``); with "learned" or "none" nothing here marks a
        position (Nemotron-H: the state-space layers beside it carry the
        order; ``rope`` is then (None, None)); a mixer named in
        ``unrotated_mixers`` likewise, in a model whose other kind rotates
        (SmallThinker's full layers), under its own scopes ``nope_qkv`` /
        ``nope_core`` / ``nope_out``. ``attn_scale`` (Granite's
        attention_multiplier, GPT-Neo's 1.0, in place of 1 / sqrt(head_dim))
        q carries after the rotation. ``local`` (traced bool scalar, GPT-Neo):
        the layer sees the trailing ``local_attention_window`` keys only, over
        a dense mask (``_windowed_attention``)."""
        import jax.numpy as jnp
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        windowed = mixer == "swa"
        nope = mixer in cfg.unrotated_mixers
        rotated = cfg.position == "rope" and not nope
        if windowed and not rotated:
            raise NotImplementedError("mixer 'swa' rotates by its own table: position 'rope'")
        if windowed and cfg.swa_window <= 0:
            raise ValueError("mixer 'swa' needs swa_window > 0")
        B, T = y.shape[:2]
        H, KV, Dh = cfg.heads_of(mixer), cfg.kv_heads, cfg.head_dim
        cos, sin = rope
        # a window layer's own scope inside each of the attention layer's
        own = lambda name: trace.scope(name) if name else contextlib.nullcontext()
        # (and an unrotated layer's, in a model whose others rotate)
        swa = lambda part: own("swa_" + part if windowed else
                               "nope_" + part if nope else None)
        with trace.scope("attn_qkv"):
            with swa("qkv"):
                if cfg.qk_norm is True:
                    q, k = y @ lw["wq"], y @ lw["wk"]
                    with trace.scope("attn_qk_norm"):
                        q = _norm(q, lw["q_norm_w"], 0, "rmsnorm", eps=cfg.norm_eps)
                        k = _norm(k, lw["k_norm_w"], 0, "rmsnorm", eps=cfg.norm_eps)
                    q, k = q.reshape(B, T, H, Dh), k.reshape(B, T, KV, Dh)
                else:
                    q = (y @ lw["wq"]).reshape(B, T, H, Dh)
                    k = (y @ lw["wk"]).reshape(B, T, KV, Dh)
                v = (y @ lw["wv"]).reshape(B, T, KV, Dh)
                if cfg.attn_qkv_bias:
                    q = q + lw["b_q"].astype(y.dtype).reshape(H, Dh)
                    k = k + lw["b_k"].astype(y.dtype).reshape(KV, Dh)
                    v = v + lw["b_v"].astype(y.dtype).reshape(KV, Dh)
            if cfg.qk_norm == "head":
                with trace.scope("attn_qk_norm"):
                    q = _head_norm(q, lw["q_norm_w"], "rmsnorm", cfg.norm_eps)
                    k = _head_norm(k, lw["k_norm_w"], "rmsnorm", cfg.norm_eps)
            if rotated:
                with own("swa_rope" if windowed else "rope_yarn" if cfg.rope_yarn else None):
                    q = apply_rope(q, cos, sin, interleaved=cfg.rope_interleaved)
                    k = apply_rope(k, cos, sin, interleaved=cfg.rope_interleaved)
            if cfg.attn_scale:
                # the kernels always divide by sqrt(Dh): q carries the rest
                q = q * jnp.asarray(cfg.attn_scale * math.sqrt(Dh), q.dtype)
        # Name the KV residuals so remat_policy="offload_kv_host" can park
        # them in host RAM between fwd and bwd (FPDT SequenceChunk offload,
        # reference sequence/fpdt_layer.py:462; XLA schedules the transfers
        # and double-buffers the prefetch). q joins for the selective-save
        # policies (save_attn_seams / save_ffn). No-op under other policies.
        q = checkpoint_name(q, "q")
        k = checkpoint_name(k, "kv")
        v = checkpoint_name(v, "kv")
        alibi = (alibi_slopes(H) * cfg.alibi_slope_scale
                 if cfg.position == "alibi" else None)
        with trace.scope("attn_core"), swa("core"):
            if cfg.local_attention_window and local is not None:
                attn = _windowed_attention(q, k, v, cfg.local_attention_window, local)
            else:
                attn = self._attention(q, k, v, alibi,
                                       window=cfg.swa_window if windowed else 0)
        attn = checkpoint_name(attn, "attn")
        with trace.scope("attn_out"), swa("out"):
            out = attn.reshape(B, T, H * Dh) @ lw["wo"]
            return out + lw["b_o"].astype(y.dtype) if cfg.attn_out_bias else out

    @property
    def dsa_index_scale(self) -> float:
        """What the indexer's weighted sum is scaled by: heads^-1/2 x dim^-1/2."""
        return (self.config.dsa_index_heads * self.config.dsa_index_dim) ** -0.5

    def dsa_index(self, lw, y, rope):
        """A learned sparse attention's indexer on the layer's normed input
        y [B, T, D], DETACHED (it reads the stream and writes nothing back
        into it): (qI [B, T, Hi, Di], kI [B, T, Di], w [B, T, Hi]), under the
        scope ``dsa_index``. ``rope``: ``rope_for("dsa", T)``."""
        import jax

        cfg = self.config
        B, T = y.shape[:2]
        Hi, Di = cfg.dsa_index_heads, cfg.dsa_index_dim
        cos_i, sin_i = rope[2:]
        with trace.scope("dsa_index"):
            yd = jax.lax.stop_gradient(y)
            qi = apply_rope((yd @ lw["dsa_wq"]).reshape(B, T, Hi, Di), cos_i, sin_i)
            ki = _norm(yd @ lw["dsa_wk"], lw["dsa_k_norm_w"], lw["dsa_k_norm_b"],
                       "layernorm", eps=cfg.norm_eps)
            ki = apply_rope(ki[:, :, None, :], cos_i, sin_i)[:, :, 0]
            return qi, ki, yd @ lw["dsa_ww"]

    def _dsa(self, lw, y, rope):
        """A learned sparse attention on the block's normed input y [B, T, D]
        -> (out [B, T, D], what the layer reports): GQA projections with the
        per-head q/k RMSNorm (``qk_norm`` "head") and the model's rotation
        (M-RoPE under ``mrope``), an indexer on the DETACHED input (``dsa_wq``:
        ``dsa_index_heads`` queries of ``dsa_index_dim``; ``dsa_wk``: one key
        with a LayerNorm, both rotated whole by the temporal stream;
        ``dsa_ww``: a weight a head), and ``ops/dsa``'s ``select`` and
        ``attend``: the ``dsa_topk`` best keys a query, the softmax over them,
        the indexer's KL loss. ``rope`` = (cos, sin, the indexer's cos, sin)
        (``rope_for``). Reports ``dsa_kl`` (the layer's loss, the mean over its tokens) and the
        counters ``dsa_selected_min`` / ``dsa_selected_max`` / ``dsa_pairs`` /
        ``dsa_tied_chunks`` / ``dsa_block_visit_share``. Scopes: ``dsa_index`` and ``dsa_select``
        inside ``attn_qkv``, ``dsa_core`` and ``dsa_kl`` inside ``attn_core``."""
        import jax
        from jax.ad_checkpoint import checkpoint_name

        from ..ops import dsa

        cfg = self.config
        if cfg.qk_norm not in ("head", False) or cfg.position != "rope" or not cfg.causal:
            raise NotImplementedError(
                "mixer 'dsa' is causal GQA with a per-head q/k norm or none "
                "(qk_norm 'head'), rotated (position 'rope')")
        B, T = y.shape[:2]
        H, KV, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        cos, sin = rope[:2]
        with trace.scope("attn_qkv"):
            q = (y @ lw["wq"]).reshape(B, T, H, Dh)
            k = (y @ lw["wk"]).reshape(B, T, KV, Dh)
            v = (y @ lw["wv"]).reshape(B, T, KV, Dh)
            if cfg.qk_norm == "head":
                with trace.scope("attn_qk_norm"):
                    q = _head_norm(q, lw["q_norm_w"], "rmsnorm", cfg.norm_eps)
                    k = _head_norm(k, lw["k_norm_w"], "rmsnorm", cfg.norm_eps)
            with trace.scope("mrope"):
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            qi, ki, w = self.dsa_index(lw, y, rope)
            # the scores and the search, a chunk of queries at a time
            scale = self.dsa_index_scale
            mask_t, found = dsa.select(qi, ki, w, cfg.dsa_topk, scale, scope=trace.scope)
        q = checkpoint_name(q, "q")
        k = checkpoint_name(k, "kv")
        v = checkpoint_name(v, "kv")
        with trace.scope("attn_core"):
            attn, loss = dsa.attend(q, k, v, qi, ki, w, mask_t, index_scale=scale,
                                    scope=trace.scope)
        attn = checkpoint_name(attn, "attn")
        with trace.scope("attn_out"):
            out = attn.reshape(B, T, H * Dh) @ lw["wo"]
        return out, {"dsa_kl": loss / (B * T),
                     **{"dsa_" + name: x for name, x in found.items()}}

    def _gated_attention(self, lw, y, rope):
        """Qwen3-Next's full-attention mixer on the normed block input
        y [B, T, D] -> [B, T, D]: ``[q | gate]`` per head from one projection,
        RMSNorm of q and k per HEAD over its ``head_dim`` (the block norm's
        kind and eps: a zero-centred gain here) before RoPE on the leading
        ``rotary_dim``, causal GQA attention, ``(o * sigmoid(gate)) Wo``."""
        import jax
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        B, T = y.shape[:2]
        H, KV, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        cos, sin = rope
        with trace.scope("attn_qkv"):
            qg = (y @ lw["wq"]).reshape(B, T, H, 2 * Dh)
            q, gate = qg[..., :Dh], qg[..., Dh:]
            k = (y @ lw["wk"]).reshape(B, T, KV, Dh)
            v = (y @ lw["wv"]).reshape(B, T, KV, Dh)
            with trace.scope("attn_qk_norm"):
                q = _head_norm(q, lw["q_norm_w"], cfg.norm, cfg.norm_eps)
                k = _head_norm(k, lw["k_norm_w"], cfg.norm, cfg.norm_eps)
            q = apply_rope(q, cos, sin, interleaved=cfg.rope_interleaved)
            k = apply_rope(k, cos, sin, interleaved=cfg.rope_interleaved)
        q = checkpoint_name(q, "q")
        k = checkpoint_name(k, "kv")
        v = checkpoint_name(v, "kv")
        with trace.scope("attn_core"):
            attn = self._attention(q, k, v, None)
        attn = checkpoint_name(attn, "attn")
        with trace.scope("attn_out"):
            with trace.scope("attn_gate"):
                attn = attn * jax.nn.sigmoid(gate)
            return attn.reshape(B, T, H * Dh) @ lw["wo"]

    def _sconv(self, lw, y, rope):
        """The gated short-convolution mixer (LFM2's ``conv`` layers;
        ``ops/short_conv.py``) on the normed block input y [B, T, D] ->
        [B, T, D]; ``rope`` is not used (the taps carry the order):
        ``[B | C | x] = y W_in`` (three blocks of D), ``u = B * x``, a causal
        depthwise convolution of ``sconv_taps`` taps over u along each
        sequence (zeros before its first position), ``(C * that) W_out``. No
        bias, no activation. Under the outer scopes of an attention layer so
        that a reader's sums by layer hold, its own nested inside:
        ``sconv_in`` (in ``attn_qkv``), ``sconv_mix`` (in ``attn_core``: the
        pass between the projections, ``sconv_mix``) and ``sconv_out`` (in
        ``attn_out``). A sequence-parallel mesh is refused: the taps of a
        shard's first rows reach the last ``sconv_taps - 1`` rows of the
        shard before it, a halo nothing carries."""
        from ..ops.short_conv import sconv_mix

        del rope
        if self._sp_mesh()[0] > 1:
            raise NotImplementedError(
                "the gated short convolution (mixer 'sconv') under a "
                "sequence-parallel mesh: a shard's first rows need the last "
                f"{self.config.sconv_taps - 1} rows of the shard before it, a "
                "halo nothing carries; run it with seq = 1")
        with trace.scope("attn_qkv"), trace.scope("sconv_in"):
            bcx = y @ lw["sconv_w_in"]
        with trace.scope("attn_core"), trace.scope("sconv_mix"):
            mixed = sconv_mix(bcx, lw["sconv_w"])
        with trace.scope("attn_out"), trace.scope("sconv_out"):
            return mixed @ lw["sconv_w_out"]

    def _ssm(self, lw, y, rope):
        """The Mamba-2 state-space mixer (Nemotron-H's ``M`` layers;
        ``ops/ssd.py``) on the normed block input y [B, T, D] -> [B, T, D];
        ``rope`` is not used (the convolution and the decay carry the order).
        Shapes from ``ssm_*``: H heads of P, G groups of a state of N.
        ``[z | xBC | dt] = y W_in`` (inner = H P, inner + 2 G N, H wide);
        ``xBC = silu(conv(xBC) + b)`` (``ops/ssm_conv.py``), causal taps a channel,
        zero before position 0; ``dt = softplus(dt + dt_bias)`` unclamped, ``A =
        -exp(A_log)``, both float32; the scan (``ssd_chunked``); its epilogue
        (``ops/ssm_gate_norm.py``): the skip ``o + D x``, ``* silu(z)`` and
        THEN an RMSNorm over each of the G groups of inner / G channels under
        one gain; ``W_out``. Under the outer scopes of an attention layer so
        that a reader's sums by layer hold, its own nested inside: ``ssm_in``,
        ``ssm_conv`` and ``ssm_gates`` (in ``attn_qkv``), ``ssm_scan`` (in
        ``attn_core``), ``ssm_out_norm`` and ``ssm_out`` (in ``attn_out``). A
        sequence-parallel mesh is refused: a shard's scan starts from the
        state the shard before it ends in, which nothing carries."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from ..ops.ssd import ssd_chunked
        from ..ops.ssm_conv import ssm_conv
        from ..ops.ssm_gate_norm import ssm_gate_norm
        from ..parallel.mesh import kernel_activation_spec, shard_kernel

        del rope
        cfg = self.config
        if self._sp_mesh()[0] > 1:
            raise NotImplementedError(
                "the state-space scan (mixer 'ssm') under a sequence-parallel "
                "mesh: a shard's scan and convolution start from the state and "
                "the tail of the shard before it, which nothing carries; run "
                "it with seq = 1")
        B, T = y.shape[:2]
        H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
        inner = H * P
        f32 = jnp.float32
        with trace.scope("attn_qkv"):
            with trace.scope("ssm_in"):
                zxbcdt = y @ lw["ssm_w_in"]
            with trace.scope("ssm_conv"):
                # xBC where it lies, z and dt through; per device like the scan
                rows = kernel_activation_spec(zxbcdt.shape)
                z, x, Bm, Cm, dt = shard_kernel(
                    functools.partial(ssm_conv, start=inner, widths=(inner, G * N, G * N)),
                    (rows, PartitionSpec(), PartitionSpec()), (rows,) * 5,
                )(zxbcdt, lw["ssm_conv_w"], lw["ssm_conv_b"])
            with trace.scope("ssm_gates"):
                dt = jax.nn.softplus(dt.astype(f32) + lw["ssm_dt_bias"].astype(f32))
                A = -jnp.exp(lw["ssm_A_log"].astype(f32))
        with trace.scope("attn_core"), trace.scope("ssm_scan"):
            # each device runs the scan on its own rows (the skip is the epilogue's)
            wide = kernel_activation_spec((B, T, H, P))
            rows = kernel_activation_spec(dt.shape)
            o = shard_kernel(ssd_chunked, (wide, rows, PartitionSpec(), wide, wide), wide)(
                x.reshape(B, T, H, P), dt, A, Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N))
        with trace.scope("attn_out"):
            with trace.scope("ssm_out_norm"):
                # the skip, the gate FIRST, then a norm over each group's
                # channels; z's values read where the projection left them
                flat = kernel_activation_spec(z.shape)
                o = shard_kernel(
                    lambda o, x, z, zxbcdt, D, gain: ssm_gate_norm(
                        o, x, z, D, gain, G, cfg.norm_eps, z_in=zxbcdt),
                    (flat,) * 4 + (PartitionSpec(),) * 2, flat,
                )(o.reshape(B, T, inner), x, z, zxbcdt, lw["ssm_D"].astype(f32),
                  lw["ssm_norm_w"].astype(f32))
            with trace.scope("ssm_out"):
                return o @ lw["ssm_w_out"]

    def _mla(self, lw, y, rope):
        """The latent-attention mixer (DeepSeek-V2/V3 MLA without query
        compression) on the normed block input y [B, T, D] -> [B, T, D].
        ``q = y Wq`` per head [content dc | rope dr]; ``[c | k_r] = y Wkv_a``:
        the latent (``mla_kv_rank`` wide) and ONE rotary key a token, shared
        by all heads; ``[k_c | v] = RMSNorm(c) Wkv_b`` per head. RoPE turns
        q's rope dims and k_r (pairs in place: ``rope_interleaved``), the key
        is ``[k_c | k_r]``, scores are dc + dr wide (scaled by its root),
        values ``mla_v_dim``. Its own scopes nest in ``attn_qkv``: ``mla_q``,
        ``mla_kv_down``, ``mla_kv_norm``, ``mla_kv_up``, ``mla_rope`` (the
        rotation, k_r's broadcast over the heads, the concatenations). Under
        ``unrotated_mixers`` (Kimi Linear's ``mla_use_nope``: the KDA layers'
        convolutions and decays carry the order) NOTHING is rotated: q's
        ``dr`` dims and k_r stay as projected, ``mla_rope`` keeps the
        broadcast and the concatenation alone, and the layer opens
        ``nope_qkv`` / ``nope_core`` / ``nope_out`` as ``_gqa``'s unrotated
        layers do."""
        import jax.numpy as jnp
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        B, T = y.shape[:2]
        H = cfg.n_heads
        r, dc, dr, dv = (cfg.mla_kv_rank, cfg.mla_qk_content_dim,
                         cfg.mla_qk_rope_dim, cfg.mla_v_dim)
        cos, sin = rope
        nope = cos is None          # ``rope_for`` hands this kind no table
        own = lambda part: trace.scope("nope_" + part) if nope else contextlib.nullcontext()
        with trace.scope("attn_qkv"), own("qkv"):
            with trace.scope("mla_q"):
                q = (y @ lw["mla_wq"]).reshape(B, T, H, dc + dr)
            with trace.scope("mla_kv_down"):
                down = y @ lw["mla_wkv_a"]
                c, k_r = down[..., :r], down[..., r:]
            with trace.scope("mla_kv_norm"):
                c = _norm(c, lw["mla_kv_norm_w"], 0, "rmsnorm", eps=cfg.norm_eps)
            with trace.scope("mla_kv_up"):
                kv = (c @ lw["mla_wkv_b"]).reshape(B, T, H, dc + dv)
                k_c, v = kv[..., :dc], kv[..., dc:]
            with trace.scope("mla_rope"):
                if nope:
                    k_r = k_r[:, :, None, :]
                else:
                    q_r = apply_rope(q[..., dc:], cos, sin, interleaved=cfg.rope_interleaved)
                    k_r = apply_rope(k_r[:, :, None, :], cos, sin,
                                     interleaved=cfg.rope_interleaved)
                    q = jnp.concatenate([q[..., :dc], q_r], axis=-1)
                k = jnp.concatenate(
                    [k_c, jnp.broadcast_to(k_r, (B, T, H, dr))], axis=-1)
        q = checkpoint_name(q, "q")
        k = checkpoint_name(k, "kv")
        v = checkpoint_name(v, "kv")
        with trace.scope("attn_core"), own("core"):
            attn = self._attention(q, k, v, None)            # [B, T, H, dv]
        attn = checkpoint_name(attn, "attn")
        with trace.scope("attn_out"), own("out"):
            return attn.reshape(B, T, H * dv) @ lw["mla_wo"]

    def _kda(self, lw, y, rope):
        """The Kimi Delta Attention mixer (``ops/kda.py``) on the normed
        block input y [B, T, D] -> ([B, T, D], ``found``: the mean and the
        least, over one chunk in 16, the heads and the key channels, of what
        a state's row keeps over one chunk of the rule);
        ``rope`` is not used. Shapes from ``kda_*``: H heads of dk (q, k) and
        dv. ``[q | k | v] = y W_qkv``, each through a causal depthwise
        convolution of ``kda_conv_kernel`` taps without bias and SiLU; q
        and k l2-normed per head, q times ``dk ** -0.5`` (all of that is
        ``ops/kda.py`` ``kda_prologue``: on a TPU at heads of whole lane tiles
        one kernel a pass, ``kda_prologue_fwd`` / ``kda_prologue_bwd``, which
        reads the projection's columns where they lie and writes q, k, v in
        the layout the rule's kernels read); ``beta =
        sigmoid(y W_beta)`` [H]; ``g = -exp(A_log[h]) softplus(y W_fa W_fb +
        dt_bias)`` [H, dk], a log-decay for every key channel; the chunked
        rule; ``w o / rms(o) sigmoid(y W_ga W_gb)`` per head; ``W_out``. Under
        the outer scopes of an attention layer with its own nested inside:
        ``kda_conv`` (``kda_prologue``: the convolution, SiLU and the l2
        norms, nothing else of q, k or v), ``kda_gates``
        (beta, g, the statistics of g), ``kda_scan``, ``kda_out_norm``. g,
        beta, the norms and the rule's state are float32; the projections and
        the rule's matmul operands are the compute dtype."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from ..ops.kda import chunk_decay, chunk_sample, kda_chunked, kda_prologue
        from ..parallel.mesh import kernel_activation_spec, shard_kernel

        del rope
        cfg = self.config
        if self._sp_mesh()[0] > 1:
            raise NotImplementedError(
                "the KDA rule (mixer 'kda') under a sequence-parallel mesh: a "
                "shard's rule and convolution start from the state and the tail "
                "of the shard before it, which nothing carries; run it with seq = 1")
        B, T = y.shape[:2]
        H, dk, dv = cfg.kda_heads, cfg.kda_key_dim, cfg.kda_value_dim
        f32 = jnp.float32
        with trace.scope("attn_qkv"):
            qkv = y @ lw["kda_w_qkv"]
            with trace.scope("kda_conv"):
                # convolution, SiLU and the l2 norms: one pass over q, k, v
                # where the projection left their columns (``kda_prologue``),
                # per device on its own rows like the rule below
                rows = kernel_activation_spec(qkv.shape)
                heads = kernel_activation_spec((B, T, H, dk))
                q, k, v = shard_kernel(
                    functools.partial(kda_prologue, heads=H, dk=dk, dv=dv),
                    (rows, PartitionSpec()), (heads,) * 3)(qkv, lw["kda_conv_w"])
            with trace.scope("kda_gates"):
                beta = jax.nn.sigmoid((y @ lw["kda_w_beta"]).astype(f32))

                def log_decay(y, w_fa, w_fb, A_log, dt_bias):
                    a = jnp.matmul(y @ w_fa, w_fb, preferred_element_type=f32)
                    return -jnp.exp(A_log.astype(f32))[:, None] * jax.nn.softplus(
                        (a + dt_bias.astype(f32)).reshape(a.shape[:2] + (H, dk)))

                leaves = [lw[name] for name in
                          ("kda_w_fa", "kda_w_fb", "kda_A_log", "kda_dt_bias")]
                g = log_decay(y, *leaves)
                # what a state's rows keep over a chunk, in the timed steps: on
                # one chunk in 16, formed AGAIN from those tokens of y. A second
                # reader of g, or of the low-rank product, moves XLA's layouts
                # on the way to the rule: 14 and 10 ms of a 676 ms step (my
                # chip runs, PR 67); nothing of the backward reads this
                kept = chunk_decay(log_decay(
                    *jax.lax.stop_gradient([chunk_sample(y)] + leaves)))
                found = {"kda_decay_mean": jnp.mean(kept), "kda_decay_min": jnp.min(kept)}
        with trace.scope("attn_core"):
            with trace.scope("kda_scan"):
                # each device runs the rule on its own rows and heads (``_gdn``)
                wide = kernel_activation_spec(q.shape, heads_dim=2)
                flat = kernel_activation_spec(beta.shape, heads_dim=2)
                o = shard_kernel(
                    kda_chunked, (wide, wide, wide, wide, flat), wide)(q, k, v, g, beta)
        with trace.scope("attn_out"):
            with trace.scope("kda_out_norm"):
                # a plain gain per head and a SIGMOID gate from a low-rank pair
                gate = jnp.matmul(y @ lw["kda_w_ga"], lw["kda_w_gb"],
                                  preferred_element_type=f32).reshape(B, T, H, dv)
                o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                      + cfg.norm_eps)
                o = (lw["kda_norm_w"].astype(f32) * o
                     * jax.nn.sigmoid(gate)).astype(y.dtype)
            return o.reshape(B, T, H * dv) @ lw["kda_w_out"], found

    def _gdn(self, lw, y, rope):
        """The Gated DeltaNet mixer (``ops/gated_delta.py``) on the normed
        block input y [B, T, D] -> [B, T, D]; ``rope`` is not used (the
        convolution and the decay carry the order). Shapes from ``gdn_*``:
        Hk key heads of dk, Hv value heads of dv, Hv a multiple of Hk.
        Under the outer scopes of an attention layer (``attn_qkv``,
        ``attn_core``, ``attn_out``), so that a reader's sums by layer hold,
        with its own nested inside: ``gdn_conv`` (``gdn_prologue``: the
        convolution, SiLU, the l2 norms of q and k, the repeat to Hv heads
        and z's channels handed on, one pass forward and one backward where
        the kernels run: on a TPU wherever some group of key heads is whole
        lane tiles of ``qkvz``, a key head at 128 / 128, a pair at 96 / 192),
        ``gdn_gates`` (beta and the log-decay g), ``gdn_scan`` (the chunked
        rule), ``gdn_out_norm``. g, beta, the norms and the rule's state are
        float32; the projections and the rule's matmul operands are the
        compute dtype."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec

        from ..ops.gated_delta import gated_delta_chunked, gdn_prologue
        from ..parallel.mesh import kernel_activation_spec, shard_kernel

        cfg = self.config
        B, T = y.shape[:2]
        Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
        dk, dv = cfg.gdn_key_dim, cfg.gdn_value_dim
        rep = Hv // Hk
        f32 = jnp.float32
        with trace.scope("attn_qkv"):
            # a key head's channels side by side: q, k, its rep v's, their z's
            qkvz = y @ lw["w_qkvz"]
            ba = (y @ lw["w_ba"]).reshape(B, T, Hk, 2 * rep)
            b, a = ba[..., :rep].reshape(B, T, Hv), ba[..., rep:].reshape(B, T, Hv)
            with trace.scope("gdn_conv"):
                # convolution, SiLU, the l2 norms and the repeat to Hv heads:
                # one pass over q, k, v where they lie (``gdn_prologue``),
                # per device on its own rows like the rule below (all heads:
                # ``conv_w`` keeps the checkpoint's channel order, which no
                # split over heads cuts whole)
                rows = kernel_activation_spec(qkvz.shape)
                heads = kernel_activation_spec((B, T, Hv, dk))
                q, k, v, z = shard_kernel(
                    functools.partial(gdn_prologue, key_heads=Hk, dk=dk, dv=dv),
                    (rows, PartitionSpec()), (heads,) * 4,
                )(qkvz, lw["conv_w"])
            with trace.scope("gdn_gates"):
                beta = jax.nn.sigmoid(b.astype(f32))
                if cfg.gdn_beta_scale != 1.0:
                    # eigenvalues of I - beta k k^T down to -1
                    beta = cfg.gdn_beta_scale * beta
                g = -jnp.exp(lw["A_log"].astype(f32)) * jax.nn.softplus(
                    a.astype(f32) + lw["dt_bias"].astype(f32))
        with trace.scope("attn_core"):
            with trace.scope("gdn_scan"):
                # each device runs the rule on its own rows and heads, like a
                # kernel (they are independent): left to XLA's partitioner
                # under ZeRO-3 weights and full remat, the 8-device CPU mesh
                # computed a wrong forward (loss off by 5e-3, PR 33)
                wide = kernel_activation_spec(q.shape, heads_dim=2)
                flat = kernel_activation_spec(g.shape, heads_dim=2)
                o = shard_kernel(
                    gated_delta_chunked,
                    (wide, wide, wide, flat, flat), wide)(q, k, v, g, beta)
        with trace.scope("attn_out"):
            with trace.scope("gdn_out_norm"):
                # a plain gain per head, then the z gate: w * o/rms(o) * silu(z)
                o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                      + cfg.norm_eps)
                o = (lw["gdn_norm_w"].astype(f32) * o * jax.nn.silu(
                    z.astype(f32))).astype(y.dtype)
            return o.reshape(B, T, Hv * dv) @ lw["w_out"]

    def _ffn(self, lw, y2, moe_on, ffn=None, router_x=None):
        """The (MoE or dense) feed-forward on ``y2``; the block's skeleton
        (``layer_apply``) norms and adds. ``router_x``: what a routed layer's
        router reads where that is not ``y2`` (``moe_router_input`` "block":
        the block's input; ``moe.layer.moe_layer``). Returns (ff, moe_aux,
        stats): ``stats`` is None for a dense model, else this layer's ``expert_tokens`` [E] int32 (the
        token-choices the router gave each of ALL its experts), ``router_prob``
        [E] (mean router probability), ``held_rows`` (the token-choices the
        experts held here computed: all of them unless this is a rank's
        share) and ``overflow_rows`` (held rows that did not fit the buffer
        and were dropped); a rank's share also ``visited_rows`` (positions of
        its buffer the row passes walked). ``moe_aux`` is the layer's own
        balancing loss; for ``moe_aux="all_choices"`` it is divided by the
        layer count, which is HF's loss exactly at one layer and a per-layer
        form of it otherwise (``loss_and_stats`` computes the exact
        cross-layer form from the stats where it has them)."""
        import jax
        import jax.numpy as jnp
        from jax.ad_checkpoint import checkpoint_name

        cfg = self.config
        dtype = y2.dtype
        aux = jnp.zeros((), jnp.float32)
        stats = None
        gate_act = gate_fn(cfg.activation)      # None: an ungated unit
        if (ffn or cfg.pattern[0][1]) == "moe":
            from ..moe.layer import held_buffer_rows, moe_layer

            expert_params = {name[4:]: lw[name] for name in lw
                             if name.startswith("moe_")
                             and name not in ("moe_gate", "moe_select_bias")
                             and not name.startswith("moe_shared")}

            def moe_branch(y2):
                # scanned=True: layer_apply always runs under stack_apply's
                # lax.scan, where "auto" takes the capacity path
                # (moe/resolve_moe_impl)
                share = {}
                if cfg.experts_held != cfg.n_experts:
                    share = dict(expert_first=cfg.expert_first, buffer_rows=held_buffer_rows(
                        math.prod(y2.shape[:-1]), cfg.moe_top_k, cfg.experts_held,
                        cfg.n_experts, cfg.moe_held_rows_factor))
                res = moe_layer(lw["moe_gate"], expert_params, y2, k=cfg.moe_top_k,
                                capacity_factor=cfg.capacity_factor, activation=cfg.activation,
                                impl=cfg.moe_impl, normalize_weights=cfg.moe_norm_topk,
                                scanned=True, aux=cfg.moe_aux, score=cfg.moe_score,
                                select_bias=lw.get("moe_select_bias"),
                                weight_scale=cfg.moe_weight_scale, router_x=router_x,
                                **share)
                aux = res.aux_loss
                if cfg.moe_aux == "all_choices":
                    aux = aux / cfg.n_layers
                counts = res.metadata["expert_counts"].astype(jnp.int32)
                stats = {
                    "expert_tokens": counts,
                    "router_prob": res.metadata["router_prob"],
                    "held_rows": jnp.asarray(res.metadata.get(
                        "held_rows", counts.sum()), jnp.int32),
                    "overflow_rows": jnp.asarray(res.metadata.get(
                        "overflow_rows", 0), jnp.int32)}
                if "expert_weight" in res.metadata:
                    stats["expert_weight"] = res.metadata["expert_weight"]
                if "visited_rows" in res.metadata:
                    stats["visited_rows"] = jnp.asarray(
                        res.metadata["visited_rows"], jnp.int32)
                return res.output, aux, stats

            if moe_on is None:
                ff, aux, stats = moe_branch(y2)
            elif router_x is not None:
                raise NotImplementedError(
                    "a router that reads the block's input (router_x) under "
                    "moe_layer_pattern's per-layer flag: that flag varies ONE kind "
                    "of layer, whose router reads what its experts read")
            else:
                def dense_branch(y2):
                    # expert slot 0 carries the dense FFN of interleaved
                    # dense layers (Megatron --expert-interval import)
                    up = y2 @ expert_params["w_up"][0].astype(dtype)
                    if "b_up" in expert_params:
                        up = up + expert_params["b_up"][0].astype(dtype)
                    if gate_act:
                        g = y2 @ expert_params["w_gate"][0].astype(dtype)
                        if "b_gate" in expert_params:
                            g = g + expert_params["b_gate"][0].astype(dtype)
                        hh = gate_act(g) * up
                    else:
                        hh = activation_fn(cfg.activation)(up)
                    out = hh @ expert_params["w_down"][0].astype(dtype)
                    if "b_down" in expert_params:
                        out = out + expert_params["b_down"][0].astype(dtype)
                    return out, jnp.zeros((), jnp.float32), _no_routing_stats(
                        cfg.n_experts, cfg.moe_select_bias,
                        cfg.experts_held != cfg.n_experts)

                from ..parallel.mesh import inside_manual_region

                if inside_manual_region():
                    # under a partial-manual region (pipeline stage) a cond
                    # around the MoE dispatch CHECK-fails XLA's partitioner;
                    # compute both branches and select — the dense branch
                    # is one FFN, small next to the expert compute
                    ff, aux, stats = jax.tree.map(
                        lambda m, d: jnp.where(moe_on, m, d),
                        moe_branch(y2), dense_branch(y2))
                else:
                    ff, aux, stats = jax.lax.cond(moe_on, moe_branch, dense_branch, y2)
            if cfg.moe_shared_expert_ff > 0:
                # the shared expert: a dense MLP of the experts' form every token runs,
                # added through a per-token sigmoid gate (Qwen2-MoE,
                # Qwen3-Next) or as it is (DeepSeek-V3: moe_shared_gate "none")
                with trace.scope("moe_shared"):
                    if gate_act:
                        inner = gate_act(y2 @ lw["moe_shared_w_gate"]) * (
                            y2 @ lw["moe_shared_w_up"])
                    else:       # ungated (Nemotron-H's relu2): W2 act(W1 y)
                        inner = activation_fn(cfg.activation)(y2 @ lw["moe_shared_w_up"])
                    shared = inner @ lw["moe_shared_w_down"]
                    if cfg.moe_shared_gate == "sigmoid":
                        shared = jax.nn.sigmoid(
                            y2 @ lw["moe_shared_gate"]).astype(ff.dtype) * shared
                    ff = ff + shared
        elif gate_act:
            # Tagged so remat_policy="save_ffn" can keep the two big FFN
            # projections (the bulk of layer FLOPs) out of the backward
            # recompute; the elementwise silu/mul re-derives from them free.
            gate = checkpoint_name(y2 @ lw["w_gate"], "ffn_gate")
            up = checkpoint_name(y2 @ lw["w_up"], "ffn_up")
            ff = (gate_act(gate) * up) @ lw["w_down"]
        elif cfg.mlp_bias:
            act = activation_fn(cfg.activation)
            ff = act(y2 @ lw["w_up"] + lw["b_up"].astype(dtype)) @ lw["w_down"] + lw["b_down"].astype(dtype)
        else:
            act = activation_fn(cfg.activation)
            ff = act(y2 @ lw["w_up"]) @ lw["w_down"]
        return ff, aux, stats

    @staticmethod
    def _sp_mesh():
        """(sp_degree, mesh) from the live topology; (1, None) when no
        sequence-parallel axis is active."""
        from ..parallel.mesh import get_topology, topology_is_initialized

        if not topology_is_initialized():
            return 1, None
        topo = get_topology()
        return topo.size("seq"), topo.mesh

    def _attention(self, q, k, v, alibi, window: int = 0):
        """Core attention, sequence-parallel when the mesh has a "seq" axis.
        ``window`` > 0 (mixer "swa"): a query sees itself and the
        ``window - 1`` keys before it; one device's sequences only.

        Ulysses (reference DistributedAttention, sequence/layer.py:331)
        engaged via shard_map inside the jitted step: activations shard
        [batch over data+fsdp, seq over "seq"], the two all-to-alls swap
        seq<->head sharding around the local flash kernel. ALiBi rides
        both SP flavors (round 5): Ulysses slices the slope vector per
        head shard, the ring adds the bias at global key positions; see
        alibi_sp_ok below for the replicated-fallback cases."""
        cfg = self.config
        sp, mesh = self._sp_mesh()
        if window and sp > 1:
            raise NotImplementedError(
                "windowed attention (mixer 'swa') under a sequence-parallel "
                "mesh is not implemented: neither Ulysses' local kernel call "
                "nor the ring's hops carry a window")
        if (cfg.remat and cfg.remat_policy == "save_flash_lse"
                and alibi is None and sp <= 1 and cfg.causal
                and not cfg.local_attention_window and not window):
            # save_flash_lse: route through the lse-emitting kernel so the
            # policy has residuals to save — the stock flash kernel's
            # custom-vjp residuals are anonymous, which is exactly why
            # save_attn_seams regressed (it paid HBM for the named "attn"
            # seam while the flash forward still re-ran in backward to
            # rebuild its out+lse residuals). SXT_LSE_INTERPRET=1 drives
            # the kernel in interpret mode for CPU parity tests.
            import os

            from ..ops.flash_attention import (flash_attention_remat,
                                               flash_lse_ok)

            interp = bool(os.environ.get("SXT_LSE_INTERPRET"))
            if interp or flash_lse_ok(q, k, cfg.causal):
                return flash_attention_remat(q, k, v, causal=True,
                                             interpret=interp)
            from ..utils.logging import warning_once

            warning_once(
                "remat_policy=save_flash_lse: shapes/backend do not qualify "
                "for the lse flash kernel (head_dim 64/128, causal, Pallas "
                "backend) — attention takes the standard path and the "
                "policy saves nothing for this layer")
        if sp > 1:
            # The shard_map's batch spec needs the global batch divisible by
            # the data x fsdp extent; callers outside the training layout
            # (e.g. a 1-prompt inference forward while a seq mesh is live)
            # fall back to replicated attention rather than failing to trace.
            dp = int(mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1))
            if q.shape[0] % dp:
                from ..utils.logging import warning_once

                # sxt: ignore[SXT005] batch sizes are bounded by the shape-bin ladder; mesh extent is fixed
                warning_once(
                    f"sequence-parallel attention skipped: batch {q.shape[0]} "
                    f"not divisible by data*fsdp={dp} (replicated fallback)")
                sp = 1
        H_all, KV_all = q.shape[2], k.shape[2]
        # ALiBi composes with SP (round 5): Ulysses scatters WHOLE heads, so
        # each device's head block takes its own slope slice; the ring path
        # adds the bias with global kv positions. Falls back to replicated
        # attention when head counts don't split evenly (the uneven-head pad
        # path would misalign slope indices), for bidirectional ALiBi, or
        # with a live tensor axis (the slope slice would also need the
        # tensor-rank head offset — not wired; replicated attention under
        # TP still shards heads and slopes consistently via auto sharding).
        tp_live = int(mesh.shape.get("tensor", 1)) > 1 if sp > 1 else False
        alibi_sp_ok = (alibi is not None and sp > 1 and cfg.causal
                       and not tp_live
                       and (cfg.sp_attention == "ring"
                            or (H_all % sp == 0 and KV_all % sp == 0)))
        if sp > 1 and alibi is not None and not alibi_sp_ok:
            from ..utils.logging import warning_once

            warning_once(
                "mesh seq > 1 with an ALiBi model: this shape can't ride "
                "the SP paths (uneven heads under Ulysses, a live tensor "
                "axis, or bidirectional) — attention stays replicated")
        if sp <= 1 or (alibi is not None and not alibi_sp_ok):
            return causal_attention(q, k, v, attention_impl=cfg.attention_impl,
                                    alibi=alibi, causal=cfg.causal, window=window)
        import functools as ft

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..parallel.sequence import ulysses_attention

        # Ragged T (e.g. T-1 from next-token label shifting): pad the seq
        # dim up to a multiple of sp. Padded keys sit at positions past
        # every real query, so the causal mask zeroes their influence;
        # padded query rows are sliced away.
        T0 = q.shape[1]
        pad = -T0 % sp
        if pad and not cfg.causal:
            # bidirectional attention would attend INTO pad keys — no mask
            # hides them without segment ids; keep replicated attention
            return causal_attention(q, k, v, attention_impl=cfg.attention_impl,
                                    alibi=alibi, causal=False)
        if pad:
            p4 = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
            q, k, v = p4(q), p4(k), p4(v)
        # With an active tensor axis the head dim stays tensor-sharded
        # through the manual region: each (seq, tensor) device holds
        # [B/dp, T/sp, H/tp, D] and the Ulysses a2a over "seq" swaps to
        # [B/dp, T, H/(tp*sp), D] — TP x SP composition.
        tp = int(mesh.shape.get("tensor", 1))
        head_ax = "tensor" if tp > 1 else None
        if head_ax and (q.shape[2] % tp or k.shape[2] % tp):
            from ..utils.logging import warning_once

            # sxt: ignore[SXT005] head counts and mesh extent are fixed per process — dedup cardinality 1
            warning_once(
                f"seq x tensor attention: heads ({q.shape[2]}/{k.shape[2]} kv) "
                f"not divisible by tensor={tp}; heads gather across the "
                "tensor axis inside the attention region (slower, correct)")
            head_ax = None
        spec = P(("data", "fsdp"), "seq", head_ax, None)
        slopes_all = (jnp.asarray(alibi, jnp.float32)
                      if alibi is not None else None)
        if cfg.sp_attention == "ring":
            import os

            from ..parallel.sequence import ring_attention

            # save_flash_lse x ring (ISSUE 15): drop the ring's inner
            # per-hop checkpoint so THIS layer's checkpoint policy saves
            # each hop kernel's tagged (out, lse) — backward enters the
            # dq/dkv kernels from saved lse, no forward re-run (PR 3
            # discipline per hop). Every other policy keeps the per-hop
            # checkpoint (O(T/sp · D) residuals, fwd recomputed per hop).
            lse_policy = bool(cfg.remat
                              and cfg.remat_policy == "save_flash_lse")
            if cfg.cp_use_kernel not in ("auto", "pallas", "xla"):
                # the config-section path validates this in
                # ContextParallelConfig; the low-level spelling
                # (TransformerConfig built directly) bypasses it
                raise ValueError(
                    f'cp_use_kernel must be "auto", "pallas" or "xla", '
                    f'got {cfg.cp_use_kernel!r}')
            use_kernel = {"auto": "auto", "pallas": True,
                          "xla": False}[cfg.cp_use_kernel]
            interp = bool(os.environ.get("SXT_LSE_INTERPRET"))
            sp_fn = ft.partial(ring_attention, axis_name="seq",
                               causal=cfg.causal, alibi_slopes=slopes_all,
                               kv_chunk=cfg.cp_kv_chunk,
                               use_kernel=use_kernel, interpret=interp,
                               hop_remat=not lse_policy)
        elif cfg.sp_attention == "ulysses":
            if slopes_all is None:
                local = ft.partial(causal_attention,
                                   attention_impl=cfg.attention_impl,
                                   causal=cfg.causal)
            else:
                def local(q, k, v):
                    # after the seq->head a2a, device d owns the contiguous
                    # head block [d*Hc, (d+1)*Hc) — its slope slice
                    Hc = q.shape[2]
                    idx = jax.lax.axis_index("seq")
                    sl = jax.lax.dynamic_slice_in_dim(
                        slopes_all, idx * Hc, Hc)
                    return causal_attention(
                        q, k, v, attention_impl=cfg.attention_impl,
                        alibi=sl, causal=cfg.causal)
            sp_fn = ft.partial(ulysses_attention, axis_name="seq",
                               attn_fn=local, causal=cfg.causal)
        else:
            raise ValueError(f"Unsupported sp_attention {cfg.sp_attention!r}; "
                             "use 'ulysses' or 'ring'")
        # Partial-manual over exactly the axes this region names: it can
        # then NEST inside the pipeline's manual-over-"pipe" region (the
        # reference runs Ulysses inside PP stages via its group registry,
        # utils/groups.py:633 — here SP×PP composes as nested shard_maps).
        # Inside an enclosing manual region the nested call must use the
        # CONTEXT mesh (whose outer axes are typed Manual), not the
        # concrete topology mesh.
        manual = {"data", "fsdp", "seq"} | ({"tensor"} if head_ax else set())
        from ..parallel.mesh import constraint_mesh
        from ..parallel.mesh import shard_map as _shard_map

        out = _shard_map(sp_fn, mesh=constraint_mesh(mesh),
                         in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=manual)(q, k, v)
        return out[:, :T0] if pad else out

    def stack_apply(self, stacked_layers, x, rope, ltd_mask=None,
                    layer_keep=None, layer_ids=None, with_stats=False,
                    lead=None):
        """Scan the (sub)stack of layers over x, ONCE (a looped stack,
        ``cfg.loop_steps`` > 1, calls this once a step from ``loop_apply``'s
        outer scan, over the same leaves). Returns (x, summed aux), or
        with ``with_stats`` (x, summed aux, the ROUTED layers' router stats
        stacked [routed layers, E], None for a dense model; a dense layer
        among routed ones routes nothing and has no row). A block norms where
        ``cfg.norm_order`` says ("input", "output" on the several-kinds path,
        "sandwich": ``layer_apply``).

        ``lead``: the leading layers' parameters (``params["lead"]``, stacked
        [lead_layers, ...]) of a model that has them (``cfg.lead_layers``):
        they run first, in a scan of their own, each as ``cfg.lead_kind``.

        ``ltd_mask`` [B, T] bool (True = keep): random-LTD token freezing
        for the configured middle layers.
        ``layer_keep`` [L] bool (True = run): progressive layer drop
        (reference runtime/progressive_layer_drop.py) — a dropped layer is
        an identity skip (its aux loss is zeroed too). Both masks are
        traced, so the anneal never recompiles.
        ``layer_ids`` [L_local] int32 (pipeline stages): each scanned row's
        GLOBAL layer index — per-layer pattern flags (attention_pattern,
        moe_layer_pattern, random-LTD ranges) must be derived from global
        positions, not the stage-local row number; pad rows carry
        id == n_layers and map to all-off flags."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        if layer_ids is not None and cfg.loop_steps > 1:
            raise NotImplementedError(
                f"a looped stack (loop_steps {cfg.loop_steps}) under pipeline stages "
                "(layer_ids) is not implemented: the stream has to go round the "
                "stages loop_steps times (a ring) with the final norm after the "
                "last stage of each round, and the pipe schedule hands each stage "
                "its layers once (ROADMAP R-M17)")
        # Sequence-parallel activation layout: pin hidden states to
        # [batch over data+fsdp, seq over "seq"] so per-token compute and
        # activation memory split across the seq axis (the attention inside
        # layer_apply handles the seq<->head all-to-alls).
        sp, mesh = self._sp_mesh()
        if sp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import constraint_mesh

            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(constraint_mesh(mesh),
                                 P(("data", "fsdp"), "seq", None)))
        L = jax.tree_util.tree_leaves(stacked_layers)[0].shape[0]
        LG = cfg.n_layers

        def per_layer_flags(fn):
            """[L_local] bool from a global-layer predicate; pad id -> False."""
            glob = jnp.asarray([bool(fn(i)) for i in range(LG)] + [False])
            if layer_ids is None:
                return glob[:L]
            return jnp.take(glob, jnp.asarray(layer_ids, jnp.int32))

        use_local = bool(cfg.local_attention_window and cfg.attention_pattern)
        local_flags = None
        if use_local:
            ap = cfg.attention_pattern
            local_flags = per_layer_flags(lambda i: ap[i % len(ap)] == "local")
        # Megatron --expert-interval: per-layer MoE/dense flags (cycled)
        mixed_moe = bool(cfg.n_experts > 0 and cfg.moe_layer_pattern
                         and not all(cfg.moe_layer_pattern))
        moe_flags = None
        if mixed_moe:
            mp = cfg.moe_layer_pattern
            moe_flags = per_layer_flags(lambda i: mp[i % len(mp)])

        slots = self.slots()
        plain = not (ltd_mask is not None or layer_keep is not None
                     or layer_ids is not None or use_local or mixed_moe)
        if (len(slots) > 1 or cfg.lead_layers) and not plain:
            raise NotImplementedError(
                "a layer_pattern of several kinds runs the plain stack only: "
                "random-LTD, progressive layer drop, pipeline stages, "
                "attention_pattern and moe_layer_pattern vary one kind's layers"
                " (and leading layers run before the plain stack only)")
        if cfg.lead_layers and lead is None:
            raise NotImplementedError(
                f"this model starts with {cfg.lead_layers} leading layer(s) of "
                f"kind {tuple(cfg.lead_kind)}: stack_apply needs them as "
                "lead=params['lead'] (pipeline stages do not hand them over)")
        if ltd_mask is None and layer_keep is None and not mixed_moe:
            # The scan is over PERIODS of the pattern: the body runs the
            # period's layers in order, each on its own kind's row (a one-kind
            # model: a period of one layer, the row is the scan's own slice),
            # each under its own remat, and hands out per layer what
            # layer_apply does (the router stats of the routed layers only)
            # a RoPE table a kind, built here, once, outside the scans
            ropes = {"swa": self.rope_for("swa", x.shape[-2])} if any(
                mixer == "swa" for mixer, _ in cfg.kinds_used) else {}
            ropes.update({mixer: self.rope_for(mixer, x.shape[-2])
                          for mixer in cfg.unrotated_mixers})
            if len(rope) == 4:
                # a learned sparse attention's tables (``embed``): the model's
                # own and its indexer's
                ropes["dsa"], rope = rope, rope[:2]

            def run(kind):
                # a one-kind stack of softmax attention is checkpointed
                # whole (gpt2m-train's peak memory and the norms and GELU that
                # save their inputs, PR 58, were sized under it); every other
                # layer checkpoints its two halves itself (layer_apply). The
                # forward path's only reader of ``several_kinds`` (ROADMAP
                # D14 (2))
                whole = kind[0] == "attn" and not cfg.several_kinds

                def layer_fn(h, lw, loc):
                    return self.layer_apply(
                        lw, h, ropes.get(kind[0], rope), local=loc, kind=kind,
                        remat_halves=cfg.remat and not whole)

                if cfg.remat and whole:
                    return jax.checkpoint(layer_fn, policy=_remat_policy(cfg.remat_policy))
                return layer_fn

            def period_fn(h, xs):
                rows, loc = xs if use_local else (xs, None)
                if len(slots) == 1:
                    return run(slots[0][2])(h, rows, loc)
                auxs, found = [], []
                for name, i, kind in slots:
                    h, (aux, stats) = run(kind)(
                        h, jax.tree.map(lambda a: a[i], rows[name]), None)
                    auxs.append(aux)
                    if stats is not None:
                        found.append(stats)
                return h, (jnp.stack(auxs),
                           _join_rows(found, jnp.stack) if found else None)

            if cfg.lead_layers:
                # the leading layers first, in a scan of their own
                lead_fn = run(tuple(cfg.lead_kind))
                with trace.scope("layers"):
                    x, (lead_aux, lead_stats) = jax.lax.scan(
                        lambda h, lw: lead_fn(h, lw, None), x, lead)
            xs = (stacked_layers, local_flags) if use_local else stacked_layers
            with trace.scope("layers"):      # the scan's own slicing and stacking
                x, (aux_losses, stats) = jax.lax.scan(period_fn, x, xs)
            if len(slots) > 1 and stats is not None:
                # [periods, routed slots, ...] -> [routed layers, ...]
                stats = jax.tree.map(
                    lambda a: a.reshape((-1,) + a.shape[2:]), stats)
            aux = jnp.sum(aux_losses)
            if cfg.lead_layers:
                aux = aux + jnp.sum(lead_aux)
                if lead_stats is not None:   # routed leading layers, or a "kda" one
                    stats = lead_stats if stats is None else _join_rows(
                        [lead_stats, stats], jnp.concatenate)
            return (x, aux, stats) if with_stats else (x, aux)

        if ltd_mask is not None:
            end = cfg.random_ltd_end_layer if cfg.random_ltd_end_layer >= 0 else LG - 1
            active = per_layer_flags(
                lambda i: cfg.random_ltd_start_layer <= i < end)
        else:
            active = jnp.zeros((L,), bool)
        keep_layers = (jnp.ones((L,), bool) if layer_keep is None
                       else jnp.asarray(layer_keep))
        if local_flags is None:
            local_flags = jnp.zeros((L,), bool)
        if moe_flags is None:
            moe_flags = jnp.ones((L,), bool)

        def layer_fn(h, xs):
            lw, act, keep_l, loc, moe_l = xs
            out, aux = self.layer_apply(lw, h, rope,
                                        local=(loc if use_local else None),
                                        moe_on=(moe_l if mixed_moe else None))
            if ltd_mask is not None:
                keep = jnp.logical_or(~act, ltd_mask)[..., None]   # [B,T,1]
                out = jnp.where(keep, out, h)
            out = jnp.where(keep_l, out, h)
            # a dropped layer routes nothing: its aux and stats are zero
            return out, jax.tree.map(
                lambda a: jnp.where(keep_l, a, jnp.zeros_like(a)), aux)

        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(cfg.remat_policy))
        with trace.scope("layers"):
            x, (aux_losses, stats) = jax.lax.scan(
                layer_fn, x, (stacked_layers, active, keep_layers, local_flags,
                              moe_flags))
        if mixed_moe and stats is not None and layer_ids is None:
            # a dense layer routes nothing: its all-zero row is no routed
            # layer's and enters no count over them
            mp = cfg.moe_layer_pattern
            on = [i for i in range(L) if mp[i % len(mp)]]
            stats = jax.tree.map(lambda a: a[jnp.asarray(on)], stats)
        aux = jnp.sum(aux_losses)
        return (x, aux, stats) if with_stats else (x, aux)

    def loop_apply(self, params, x, rope):
        """A looped stack (``cfg.loop_steps`` = T > 1): ``h_0 = x``, ``h_t =
        final_norm(stack(h_{t-1}))`` over the SAME ``params["layers"]`` at
        every t. Returns (exits [T, B, S, D], the T NORMED streams: what the
        next step took in and what the head and the gate read; summed aux).

        ONE outer ``lax.scan`` of T trips around ``stack_apply``'s scan: the
        program holds one block's text. ``rope`` (``embed``'s) is built once,
        outside both scans, and is the same table at every step. Each (step,
        layer) visit is checkpointed as ``stack_apply`` checkpoints a layer
        (``cfg.remat``): what a visit keeps for the backward, its input under
        policy "full", is stacked [T, L, B, S, D] by the two scans, T x a
        plain stack's, and the backward replays T x L blocks (and, under
        ``cfg.remat``, the T final norms, which keep their input). The layers are
        closed over by the outer body, so a layer's weight gradient is summed
        over its T visits in the outer scan's cotangent carry, in the dtype
        the leaves have HERE (an engine hands bf16 copies: a bf16 sum of T
        bf16 gradients). Scopes: ``loop`` (the outer scan's own work: the
        carry, slicing and stacking what the visits keep, the gradient sums),
        ``loop_norm`` (the final norm, every pass)."""
        import jax

        cfg = self.config

        def final_norm(h):
            with trace.scope("loop_norm"):
                return _norm(h, params["ln_f_w"], params["ln_f_b"], cfg.norm, eps=cfg.norm_eps)

        if cfg.remat:
            # outside the layers' checkpoints: kept as it is, the norm holds its
            # float32 copy of the stream for the backward (B x S x D x 4 bytes a
            # step); replayed, the stream as it arrived (half of that)
            final_norm = jax.checkpoint(final_norm)

        def step(h, _):
            h, aux = self.stack_apply(params["layers"], h, rope, **self._lead_of(params))
            h = final_norm(h)
            return h, (h, aux)

        with trace.scope("loop"):
            _, (exits, aux) = jax.lax.scan(step, x, None, length=cfg.loop_steps)
        return exits, aux.sum()

    def exit_distribution(self, params, exits):
        """The exit gate on the T normed streams ``exits`` [T, ..., D] ->
        (p [T, ...], H [...]) in float32: ``lam_t = sigmoid(w_g . h_t +
        b_g)``, ``p_t = lam_t prod_{j<t} (1 - lam_j)`` for t < T, ``p_T =
        prod_{j<T} (1 - lam_j)`` (what is left; ``lam_T`` is not used), and the
        entropy ``H = -sum_t p_t log p_t``. Formed from log-sigmoids, so that
        a saturated gate gives p = 0 and 0 log 0 = 0, not NaN."""
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        z = jnp.einsum("t...d,d->t...", exits.astype(f32), params["exit_gate_w"].astype(f32),
                       precision=jax.lax.Precision.HIGHEST)
        z = z + params["exit_gate_b"].astype(f32)
        log_stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)   # log prod_{j<=t} (1 - lam_j)
        before = jnp.concatenate([jnp.zeros_like(z[:1]), log_stay[:-1]])
        log_p = jnp.concatenate([jax.nn.log_sigmoid(z[:-1]) + before, log_stay[-1:]])
        p = jnp.exp(log_p)
        return p, -jnp.sum(p * jnp.where(p > 0, log_p, 0.0), axis=0)

    def _unembed(self, params, dtype):
        """Single source of truth for the unembed projection: (w [D, V],
        bias [V] fp32 or None). Bias exists only on the untied path
        (matches init())."""
        import jax.numpy as jnp

        if self.config.tie_embeddings:
            return params["embed"].T.astype(dtype), None
        bias = (params["unembed_b"].astype(jnp.float32)
                if self.config.unembed_bias else None)
        return params["unembed"].astype(dtype), bias

    def head(self, params, x, normed=False):
        """Final norm + unembed: x [.., T, D] -> logits [.., T, vocab] fp32.
        ``normed``: x is normed already (a looped stack's exits: the loop has
        applied the final norm).

        The unembed matmul keeps operands in the compute dtype and
        accumulates in fp32 (``preferred_element_type``): on TPU a bf16
        MXU matmul with fp32 accumulation, not the ~6x-slower fp32-operand
        emulation an ``astype(float32)`` on both sides would force. Under
        the fp32 CPU test path this is bit-identical to the old form."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        if not cfg.post_ln and not normed:
            with trace.scope("final_norm"):
                x = _norm(x, params["ln_f_w"], params["ln_f_b"], cfg.norm,
                          eps=cfg.norm_eps)
        with trace.scope("loss"):
            if cfg.mlm_head:
                # BERT cls head: dense + gelu + LN, tied decoder with own bias
                x = activation_fn("gelu")(x @ params["mlm_dense_w"].astype(x.dtype)
                                          + params["mlm_dense_b"].astype(x.dtype))
                x = _norm(x, params["mlm_ln_w"], params["mlm_ln_b"], cfg.norm,
                          eps=cfg.norm_eps)
            w, bias = self._unembed(params, x.dtype)
            logits = jnp.matmul(x, w, preferred_element_type=jnp.float32)
            if cfg.mlm_head:
                logits = logits + params["mlm_bias"].astype(jnp.float32)
            if bias is not None:
                logits = logits + bias
            return logits if cfg.logit_divisor == 1.0 else logits / cfg.logit_divisor

    @staticmethod
    def token_loss(logits, labels):
        """Per-batch CE pieces: (nll_sum, token_count); -100/negative = ignore."""
        import jax
        import jax.numpy as jnp

        nll, mask = Transformer.token_nll(logits, labels)
        return nll.sum(), mask.sum()

    @staticmethod
    def token_nll(logits, labels):
        """(nll [..] float32 per row, 0 where ignored; mask [..]): ``token_loss``
        before its sums."""
        import jax
        import jax.numpy as jnp

        logp = jax.nn.log_softmax(logits, axis=-1)
        mask = (labels >= 0)
        safe_labels = jnp.where(mask, labels, 0)
        nll = -jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
        return nll * mask, mask

    def _pad_vocab(self) -> bool:
        """Pad the unembed to a 128-multiple vocab inside the chunked loss?
        GPT-2's 50257 is the canonical offender: the MXU tiles lanes in 128s,
        and an unaligned contraction output pays a remainder pass. Config
        tri-state: None = auto (TPU only), True/False = forced (tests)."""
        p = self.config.pad_vocab_logits
        if p is not None:
            return bool(p) and self.config.vocab_size % 128 != 0
        if self.config.vocab_size % 128 == 0:
            return False
        import jax

        return jax.default_backend() == "tpu"

    def chunked_loss(self, params, x, labels, chunk: Optional[int] = None):
        """Final-norm + unembed + CE, streamed over seq chunks of ``chunk``
        positions (None: sized by the rows at hand, ``_loss_chunk``): peak
        logits memory is [B, chunk, vocab] instead of [B, T, vocab] (the
        dominant activation for big-vocab models). Numerically identical to
        head()+token_loss(): softmax is per-token, and when the vocab is
        padded to the 128 lane tile (``_pad_vocab``) the pad columns carry a
        -1e30 additive mask, so their softmax mass underflows to exactly zero.

        Under ``jax.grad`` the scan that computes a chunk's loss computes its
        gradient too, from the same logits (``_head_scan``): three matmuls a
        chunk, nothing recomputed, no second scan. The unembed's gradient
        sums over the chunks in a float32 carry and leaves through a float32
        stand-in for the leaf (``parallel.mesh.grad_accumulator``), which
        casts it ONCE to the leaf's dtype.

        In an engine's program whose batch is split over ZeRO axes
        (``parallel.mesh.zero_batch_axes``) the scan runs in a region that is
        manual over those axes: the weights its body closes over (final norm,
        unembed or tied embedding, bias) are gathered by hand ONCE before the
        scan, each device scans its own rows against a whole head, the
        carries are the device's unreduced gradient, and one reduce-scatter
        per weight follows the scan. Left to XLA's partitioner the gather and
        the reduction sit in the loop's body, once a chunk (47% of a four-chip
        ZeRO-3 step, PERF.md PR 27). The sums are then a per-device sum and
        one psum: the same additions in another order. The region is a
        wrapper: the body is the one-device body.
        Reference capability: chunked logits loss, sequence/fpdt_layer.py:1137.
        """
        return self._chunked_loss(params, x, labels, chunk)[:2]

    def _chunked_loss(self, params, x, labels, chunk, weights=None, normed=False):
        """:meth:`chunked_loss` and, last, (chunks, rows a chunk) of the
        scan as the device that runs it sees them. ``normed``: x is normed
        already (a looped stack's exits) and the scan applies no final norm.
        ``weights`` [B, T] float32 (a gated looped stack's exit distribution,
        x then holding loop_steps x the batch's rows): each row's loss is
        weighed by its own, the weights get the rows' losses for a cotangent,
        and the sums are (the weighed sum, the count, the rows' losses [B, T],
        which carry no gradient). That scan runs OUTSIDE the ZeRO region (its
        rows' losses are no sum over devices): on a mesh XLA's partitioner
        places the head's gather."""
        from jax.sharding import PartitionSpec

        from ..parallel import mesh as mesh_lib

        unembed = "embed" if self.config.tie_embeddings else "unembed"
        head = {k: params[k] for k in ("ln_f_w", "ln_f_b", unembed, "unembed_b")
                if k in params}
        scanned = []        # noted while the scan is traced, in the region or not

        def scan(head, grad_acc, x, labels):
            *sums, shape = self._chunked_loss_scan(head, grad_acc, x, labels, chunk,
                                                   weights, normed)
            scanned.append(shape)
            return tuple(sums)

        zero_axes = mesh_lib.zero_batch_axes(x.shape[0])
        if zero_axes and weights is None:
            sums = mesh_lib.zero_region(scan, (unembed,), zero_axes)(head, x, labels)
        else:
            # no axis to gather over: the stand-in only casts the sum
            whole = mesh_lib.grad_accumulator(head[unembed], PartitionSpec(), ())
            sums = scan(head, {unembed: whole}, x, labels)
        return (*sums, scanned[0])

    def _chunked_loss_scan(self, params, grad_acc, x, labels, chunk, weights=None,
                           normed=False):
        """:meth:`chunked_loss` on whole weights and the rows at hand.
        ``grad_acc``: a float32 stand-in for the unembed's leaf, to whose
        cotangent the scan's float32 sum of the weight's gradient goes.
        ``weights`` / ``normed``: :meth:`_chunked_loss`'s."""
        import jax.numpy as jnp

        cfg = self.config
        B, T, D = x.shape
        if chunk is None:
            chunk = self._loss_chunk(B, T) or T
        n_chunks = -(-T // chunk)
        pad = n_chunks * chunk - T
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-100)
        xc = x.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
        lc = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

        # Unembed weight/bias built ONCE outside the scan (loop-invariant,
        # via the same _unembed as head()): the scan body sees an aligned
        # [D, Vp] matmul; pad columns carry a -1e30 additive mask.
        V = cfg.vocab_size
        vpad = self._vocab_pad()
        w, bias = self._unembed(params, x.dtype)
        w_acc = (grad_acc["embed"].T if cfg.tie_embeddings
                 else grad_acc["unembed"])
        extra = None
        if vpad:
            w = jnp.pad(w, ((0, 0), (0, vpad)))
            w_acc = jnp.pad(w_acc, ((0, 0), (0, vpad)))
            extra = jnp.where(jnp.arange(V + vpad) < V, 0.0, -1e30
                              ).astype(jnp.float32)
            if bias is not None:
                extra = extra + jnp.pad(bias, (0, vpad))
        elif bias is not None:
            extra = bias
        ln = (None, None) if normed else (params["ln_f_w"], params["ln_f_b"])
        wc = None
        if weights is not None:
            wc = jnp.pad(weights.astype(jnp.float32), ((0, 0), (0, pad))
                         ).reshape(B, n_chunks, chunk).transpose(1, 0, 2)
        nll_sum, cnt, *rows = _head_scan(
            cfg.norm, cfg.norm_eps, bias is not None, *ln, w, w_acc, extra, xc, lc,
            divisor=cfg.logit_divisor, wc=wc)
        # the rows' losses back in the rows' order, the pad dropped
        rows = [r.transpose(1, 0, 2).reshape(B, n_chunks * chunk)[:, :T] for r in rows]
        return (nll_sum, cnt, *rows, (n_chunks, B * chunk))

    def _vocab_pad(self) -> int:
        """Columns the chunked loss adds to reach the 128 lane tile."""
        return -self.config.vocab_size % 128 if self._pad_vocab() else 0

    def _loss_chunk(self, B: int, T: int) -> int:
        """Positions a chunk for a loss over ``B`` sequences of ``T``
        positions; 0 = full logits. ``loss_chunk`` >= 0 is that, in
        positions; auto sizes the chunk by its rows (``auto_loss_chunk``). A
        gated looped stack asks with ``B`` = loop_steps x the batch's
        sequences (``_exit_loss``: every exit's rows in ONE scan), so its
        chunk holds a loop_steps-th of the positions a plain model's would
        and ``loss_rows`` counts loop_steps x the tokens."""
        if self.config.post_ln or self.config.mlm_head:
            # chunked_loss runs ln_f + plain unembed per chunk; the encoder
            # head shape (no final norm / MLM transform) isn't wired there
            return 0
        c = self.config.loss_chunk
        if c >= 0:
            return min(c, T)
        return auto_loss_chunk(B, T, self.config.vocab_size + self._vocab_pad())

    # -- forward -------------------------------------------------------

    def apply(self, params, input_ids):
        """input_ids [B, T] -> logits [B, T, vocab] (fp32)."""
        return self.apply_with_aux(params, input_ids)[0]

    @staticmethod
    def _lead_of(params) -> dict:
        """``stack_apply``'s ``lead`` argument, where the model has leading
        layers (a stack without them is called as it always was)."""
        return {"lead": params["lead"]} if "lead" in params else {}

    def apply_with_aux(self, params, input_ids, ltd_mask=None, layer_keep=None):
        """Returns (logits, moe_aux_loss) — aux is 0 for dense models."""
        x, rope = self.embed(params, input_ids)
        if self.config.loop_steps > 1:
            # a looped stack: the LAST exit's logits (``loop_apply`` hands out
            # all of them)
            self._plain_stack_only(ltd_mask, layer_keep)
            exits, aux = self.loop_apply(params, x, rope)
            return self.head(params, exits[-1], normed=True), aux
        x, aux = self.stack_apply(params["layers"], x, rope, ltd_mask=ltd_mask,
                                  layer_keep=layer_keep, **self._lead_of(params))
        return self.head(params, x), aux

    def _plain_stack_only(self, ltd_mask, layer_keep):
        if ltd_mask is not None or layer_keep is not None:
            raise NotImplementedError(
                "a looped stack (loop_steps > 1) runs the plain stack only: random-LTD "
                "and progressive layer drop pick layers of ONE pass")

    def update_buffers(self, old, new, stats):
        """The masters after a step, given those before it (``old``), the
        optimizer's (``new``) and the step's ``loss_and_stats`` stats: what
        the trainer keeps (``runtime/engine``'s train step calls this where a
        model has it). A model without a selection bias: ``new`` as it is. The
        selection bias is a buffer no gradient reaches, so the optimizer's
        decay of it is dropped, and it takes DeepSeek-V3's aux-free update
        instead: ``old + moe_bias_update_rate x sign(mean load - load)`` per
        routed layer, from the step's own ``moe_expert_tokens``."""
        import jax.numpy as jnp

        cfg = self.config
        if not cfg.moe_select_bias:
            return new
        lead = cfg.lead_layers if cfg.lead_layers and cfg.lead_kind[1] == "moe" else 0
        moved = cfg.moe_bias_update_rate and "moe_expert_tokens" in stats

        def carried(bias, rows_of):
            """``bias`` after the step; ``rows_of`` picks its layers' rows out
            of the step's ``moe_expert_tokens`` [routed layers, E]."""
            if not moved:
                return bias
            load = rows_of(stats["moe_expert_tokens"]).astype(jnp.float32).reshape(bias.shape)
            return bias + cfg.moe_bias_update_rate * jnp.sign(
                load.mean(axis=-1, keepdims=True) - load).astype(bias.dtype)

        out = dict(new)
        if "moe_select_bias" in new.get("lead", {}):
            out["lead"] = {**new["lead"], "moe_select_bias": carried(
                old["lead"]["moe_select_bias"], lambda rows: rows[:lead])}
        if len(cfg.pattern) == 1:
            if "moe_select_bias" in new["layers"]:
                out["layers"] = {**new["layers"], "moe_select_bias": carried(
                    old["layers"]["moe_select_bias"], lambda rows: rows[lead:])}
            return out
        # several kinds: the counters' rows run period by period over the
        # period's ROUTED slots in order; a kind's bias is [periods, layers of
        # the kind a period, E]
        routed = [name for name, _, (_, ffn) in self.slots() if ffn == "moe"]
        out["layers"] = dict(new["layers"])
        for name in dict.fromkeys(routed):
            at = jnp.asarray([j for j, n in enumerate(routed) if n == name])
            out["layers"][name] = {**new["layers"][name], "moe_select_bias": carried(
                old["layers"][name]["moe_select_bias"],
                lambda rows: rows[lead:].reshape((-1, len(routed)) + rows.shape[1:])[:, at])}
        return out

    def loss(self, params, batch, rng=None):
        """Next-token cross entropy. batch: {"input_ids": [B,T]} (+ optional
        "labels" already shifted, -100 = ignore; + optional "ltd_keep_prob"
        [B] for the random-LTD schedule)."""
        return self.loss_and_stats(params, batch, rng)[0]

    def loss_and_stats(self, params, batch, rng=None):
        """``loss`` and what the step reports beside it: a dict of small
        arrays, sums that add up over microbatches (the engine keeps
        the last step's: ``Engine.last_step_stats``). An MoE model gives
        ``moe_expert_tokens`` [routed layers, E] int32, the token-choices each
        expert of each ROUTED layer was given on this batch (a dense layer
        has no row), ``moe_held_rows`` [routed layers], the token-choices
        the experts held here computed (all, unless ``n_experts_held`` makes
        this one rank's share), and ``moe_overflow_rows`` [routed layers],
        held rows that did not fit the share's buffer and were dropped (0 for
        a model that holds every expert); a rank's share also
        ``moe_visited_rows`` [routed layers], the positions of the share's
        buffer that its row passes walked (``moe.layer.held_rows_visited``:
        over the buffer's rows the share of it that costs time); a router
        with a selection bias also ``moe_expert_weight`` [routed layers, E]
        float32, the sum of the weights of each expert's token-choices; a
        chunked loss ``loss_chunks``, the trips of its scan, and
        ``loss_rows``, the rows they held, pad rows too, on the device that
        scanned them (rows a chunk: the quotient). A learned sparse attention
        (mixer "dsa") adds its indexer's loss and gives
        ``dsa_kl`` [layers] (each layer's, the mean over its tokens),
        ``dsa_selected_min`` / ``dsa_selected_max`` (the keys a query past
        position ``dsa_topk`` - 2 holds), ``dsa_pairs`` (the (t, s) chosen),
        ``dsa_block_visit_share`` and ``dsa_tied_chunks`` (the chunks of the
        step, over all layers, whose tie rule ran its search). A looped stack
        (``loop_steps`` > 1) gives ``loop_layer_visits`` (loop_steps x layers,
        static) and, gated, ``_exit_loss``'s four (and ``loss_rows`` then counts
        loop_steps x the batch's rows: the head reads every exit);
        ``batch["position_ids"]`` [3, B, T] are M-RoPE's streams (``mrope_section``; absent: text, 0..T-1 in each)."""
        import jax.numpy as jnp

        ids = batch["input_ids"]
        if not self.config.causal:
            # encoder (MLM): no next-token shift — labels mark the masked
            # positions (-100 elsewhere); default to full-token recovery
            labels = batch.get("labels", ids)
            model_ids = ids
        elif "labels" in batch:
            labels = batch["labels"]
            model_ids = ids
        else:
            with trace.scope("embed"):
                labels = ids[:, 1:]
                model_ids = ids[:, :-1]
        ltd_mask = None
        if self.config.random_ltd and "ltd_keep_prob" in batch and rng is not None:
            import jax

            rng, sub = jax.random.split(rng)
            keep = batch["ltd_keep_prob"][0]
            ltd_mask = jax.random.uniform(sub, model_ids.shape) < keep
        layer_keep = None
        if "pld_theta" in batch and rng is not None:
            # Progressive layer drop (reference progressive_layer_drop.py:10;
            # arXiv 2010.13369): keep prob anneals to theta_t and drops
            # deeper layers more: p_l = 1 - (l/L) * (1 - theta_t).
            import jax

            rng, sub = jax.random.split(rng)
            theta = jnp.asarray(batch["pld_theta"], jnp.float32).reshape(-1)[0]
            L = self.config.n_layers
            p_keep = 1.0 - (jnp.arange(L, dtype=jnp.float32) / L) * (1.0 - theta)
            layer_keep = jax.random.uniform(sub, (L,)) < p_keep
        B, T = model_ids.shape
        cfg = self.config
        positions = batch.get("position_ids")      # M-RoPE's streams [3, B, T]
        x, rope = self.embed(params, model_ids,
                             None if positions is None else positions[..., :T])
        loops = cfg.loop_steps
        stats = {}
        if loops > 1:
            self._plain_stack_only(ltd_mask, layer_keep)
            exits, aux = self.loop_apply(params, x, rope)
            routed = None
            # static: the blocks a token's forward pass goes through
            stats["loop_layer_visits"] = jnp.asarray(loops * cfg.n_layers, jnp.int32)
        else:
            x, aux, routed = self.stack_apply(params["layers"], x, rope,
                                              ltd_mask=ltd_mask, layer_keep=layer_keep,
                                              with_stats=True, **self._lead_of(params))
        if cfg.ssm_layers:
            from ..ops.ssd import ssd_chunks

            # the chunks the state-space scans of this batch walk: a sequence's
            # chunks x sequences x state-space layers
            stats["ssm_scan_chunks"] = jnp.asarray(
                ssd_chunks(T) * B * cfg.ssm_layers, jnp.int32)
        if cfg.gdn_layers:
            from ..ops.gated_delta import CHUNK
            from ..parallel.mesh import batch_rows_a_device

            # the chunks the delta rules of this batch walk on ONE device (the
            # rule runs per device on its own rows, ``_gdn``): a sequence's
            # chunks x the device's sequences x DeltaNet layers
            stats["gdn_scan_chunks"] = jnp.asarray(
                -(-T // CHUNK) * batch_rows_a_device(B) * cfg.gdn_layers, jnp.int32)
        if cfg.kda_layers:
            # static: the rules a step walks, and the layers ``rope_for`` hands
            # a table (a softmax kind reads its own; the other mixers read none)
            stats["kda_layers"] = jnp.asarray(cfg.kda_layers, jnp.int32)
            softmax = ("attn", "swa", "gated_attn", "mla", "dsa") if cfg.position == "rope" else ()
            stats["rope_layers_rotated"] = jnp.asarray(sum(
                cfg.layers_of(mixer) for mixer in softmax
                if cfg.layers_of(mixer) and self.rope_for(mixer, 1)[0] is not None), jnp.int32)
        if routed is not None and "expert_tokens" in routed:
            stats["moe_expert_tokens"] = routed["expert_tokens"]
            stats["moe_held_rows"] = routed["held_rows"]
            stats["moe_overflow_rows"] = routed["overflow_rows"]
            if "expert_weight" in routed:
                stats["moe_expert_weight"] = routed["expert_weight"]
            if "visited_rows" in routed:
                stats["moe_visited_rows"] = routed["visited_rows"]
            if cfg.moe_aux == "all_choices":
                # HF load_balancing_loss_func: the router probabilities and
                # choices of ALL layers concatenated over tokens, so both
                # means run over L * B * T rows (every layer has B * T)
                n_layers = routed["expert_tokens"].shape[0]
                f = (routed["expert_tokens"].sum(axis=0).astype(jnp.float32)
                     / (n_layers * B * T))
                aux = cfg.n_experts * jnp.sum(f * routed["router_prob"].mean(axis=0))
        if routed is not None:
            # what the mixers found, a row a layer: a learned sparse
            # attention's counters and its indexer's loss; what the states of
            # the rules with a decay a key channel keep over a chunk
            stats.update({name: x for name, x in routed.items()
                          if name.startswith(("dsa_", "kda_"))})
            if "dsa_tied_chunks" in stats:
                stats["dsa_tied_chunks"] = stats["dsa_tied_chunks"].sum()
            if "kda_decay_mean" in stats:
                stats["kda_decay_mean"] = stats["kda_decay_mean"].mean()
                stats["kda_decay_min"] = stats["kda_decay_min"].min()
        if loops > 1 and cfg.exit_gate:
            with trace.scope("loss"):
                return self._exit_loss(params, exits, labels, aux, stats)
        if loops > 1:
            x = exits[-1]           # no gate: the loss is the last exit's
        with trace.scope("loss"):
            if self._loss_chunk(B, T):
                # sized again on the rows the device that scans them holds
                nll_sum, count, scanned = self._chunked_loss(params, x, labels, None,
                                                             normed=loops > 1)
                stats["loss_chunks"] = jnp.asarray(scanned[0], jnp.int32)
                stats["loss_rows"] = jnp.asarray(scanned[0] * scanned[1], jnp.int32)
            else:
                nll_sum, count = self.token_loss(
                    self.head(params, x, normed=loops > 1), labels)
            ce = nll_sum / jnp.maximum(count, 1)
            loss = ce + cfg.aux_loss_coef * aux
            if "dsa_kl" in stats:
                # the indexer's own: the mean over layers (and, in each, tokens)
                loss = loss + stats["dsa_kl"].mean()
            return loss, stats

    def _exit_loss(self, params, exits, labels, aux, stats):
        """A gated looped stack's loss (``TransformerConfig.exit_gate``) from
        its T normed exits [T, B, S, D]: the mean over the tokens that count of
        ``sum_t p_t CE_t - exit_entropy_coef x H(p)``, float32. The head reads
        all T exits in ONE pass over T x B x S rows (one scan of the chunked
        loss, each row weighed by its ``p_t``, which gets ``CE_t`` for a
        cotangent: ``_head_scan``; or the full logits where the loss does not
        chunk), the final norm applied already. Adds to ``stats``:
        ``loop_exit_mass`` [T] (mean p_t), ``loop_exit_ce`` [T] (mean CE_t),
        ``loop_exit_entropy`` (mean H), ``loop_expected_steps`` (mean of sum_t
        t p_t), and with a chunked loss ``loss_chunks`` / ``loss_rows``, which
        count the T x B x S rows."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        T, B, S, D = exits.shape
        with trace.scope("loop_exit"):
            p, entropy = self.exit_distribution(params, exits)        # [T, B, S], [B, S]
        labels_t = jnp.broadcast_to(labels, (T, B, S))
        if self._loss_chunk(T * B, S):
            weighed, _, nll, scanned = self._chunked_loss(
                params, exits.reshape(T * B, S, D), labels_t.reshape(T * B, S), None,
                weights=p.reshape(T * B, S), normed=True)
            stats["loss_chunks"] = jnp.asarray(scanned[0], jnp.int32)
            stats["loss_rows"] = jnp.asarray(scanned[0] * scanned[1], jnp.int32)
            nll = nll.reshape(T, B, S)
        else:
            nll, _ = self.token_nll(self.head(params, exits, normed=True), labels_t)
            with trace.scope("loop_exit"):
                weighed = jnp.sum(p * nll)
        with trace.scope("loop_exit"):
            mask = (labels >= 0).astype(jnp.float32)
            count = jnp.maximum(mask.sum(), 1.0)
            mean = lambda a: jnp.sum(a * mask, axis=(-2, -1)) / count
            loss = (weighed / count - cfg.exit_entropy_coef * mean(entropy)
                    + cfg.aux_loss_coef * aux)
            steps = jnp.arange(1, T + 1, dtype=jnp.float32)[:, None, None]
            stats.update(jax.lax.stop_gradient({
                "loop_exit_mass": mean(p), "loop_exit_ce": mean(nll),
                "loop_exit_entropy": mean(entropy),
                "loop_expected_steps": mean(jnp.sum(steps * p, axis=0))}))
        return loss, stats


# The float32 logits one chunk of the loss scan may hold, in bytes: what sizes
# a chunk (``auto_loss_chunk``) and the threshold over which the loss
# chunks at all. From the compiled train steps of the benchmark's three cells
# (PERF.md PR 32): gpt2-medium at 4 x 1024 peaks at 15.4 of a v5e's 16 GB and
# has no room for more than the 1024 rows of 50,304 columns (206 MB) it had;
# a Mistral-7B chip of a ZeRO-3 mesh gets 2048 rows of 32,768.
LOSS_CHUNK_BYTES = 256 * 1024 * 1024


def auto_loss_chunk(B: int, T: int, vocab: int) -> int:
    """Positions a chunk of the chunked loss over ``B`` sequences of ``T``
    positions and ``vocab`` (padded) columns: 0 (full logits) where the
    float32 logits ``B x T x vocab x 4`` are within ``LOSS_CHUNK_BYTES``, else
    the largest power of two whose chunk of them is; at least 1, at most
    ``T``. A chunk is sized by the ROWS it holds: at 256 rows the head's three
    matmuls sit on a v5e's ridge and every chunk re-reads the weight's float32
    gradient sum; 1024-2048 rows are over it (PERF.md PR 32)."""
    if B * T * vocab * 4 <= LOSS_CHUNK_BYTES:
        return 0
    positions = max(1, LOSS_CHUNK_BYTES // (4 * vocab * B))
    return min(T, 1 << (positions.bit_length() - 1))


def _head_logits(xn, w, extra, divisor=1.0):
    import jax.numpy as jnp

    with trace.scope("head_logits"):
        logits = jnp.matmul(xn, w, preferred_element_type=jnp.float32)
        if extra is not None:
            logits = logits + extra
        return logits if divisor == 1.0 else logits / divisor


def _head_scan(kind, eps, biased, ln_w, ln_b, w, w_acc, extra, xc, lc, divisor=1.0,
               wc=None):
    """(nll_sum, count) of final norm + unembed + CE over the chunks ``xc``
    [n, B, c, D], ``lc`` [n, B, c]; ``w`` [D, Vp] in the compute dtype,
    ``extra`` [Vp] float32 (bias and pad mask) or None. ``kind``, ``eps``: the
    final norm's (``ln_w`` None: the rows are normed already, a looped
    stack's exits, and no norm is applied); ``biased``: ``extra`` holds a bias
    that wants a gradient;
    ``divisor``: what the logits are divided by (``logit_divisor``; 1 = as
    they are), and with them their cotangent on its way to dx and dw.
    ``wc`` [n, B, c] float32: per-row weights (a gated looped stack's exit
    distribution over T x the batch's rows). The result is then (the sum of
    weight x nll, the count, the rows' own nll [n, B, c], 0 where ignored),
    the weights get ``nll x mask`` (x the sum's cotangent) for a gradient,
    and the rows' losses are statistics: no gradient goes through them.

    Not under ``grad``: the plain scan of norm, logits and ``token_loss``.
    Under ``grad`` (a ``custom_vjp``) the forward scan does the head's whole
    work once a chunk: from the same logits it takes ``dlogits = (softmax -
    onehot) * mask`` (x the row's weight), unscaled, and with it ``dx``
    (stacked, the scan's output) and the sums of the weights' gradients in
    float32 carries. The backward rule only multiplies them by ``nll_sum``'s
    cotangent. ``w`` gets no cotangent: its gradient [D, Vp] float32 is
    ``w_acc``'s, a stand-in that hands it to the leaf
    (``parallel.mesh.grad_accumulator``). Without ``wc`` and with a norm the
    traced program is what it was before either existed, op for op."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    normed = ln_w is None
    weighed = wc is not None

    def norm(x, ln_w, ln_b):
        with trace.scope("final_norm"):
            return _norm(x, ln_w, ln_b, kind, eps=eps)

    def primal(ln_w, ln_b, w, w_acc, extra, xc, lc, wc):
        def body(carry, xl):
            xch, lch, *wch = xl
            logits = _head_logits(xch if normed else norm(xch, ln_w, ln_b), w, extra, divisor)
            if weighed:
                nll, mask = Transformer.token_nll(logits, lch)
                return (carry[0] + (wch[0] * nll).sum(), carry[1] + mask.sum()), nll
            nll, cnt = Transformer.token_loss(logits, lch)
            return (carry[0] + nll, carry[1] + cnt), None

        sums, rows = jax.lax.scan(body, (jnp.zeros((), f32), jnp.zeros((), jnp.int32)),
                                  (xc, lc, wc) if weighed else (xc, lc))
        return (*sums, rows) if weighed else sums

    def fwd(ln_w, ln_b, w, w_acc, extra, xc, lc, wc):
        # float32 norm weights (what ``_norm`` computes with), so that the
        # norm's vjp hands their gradients out unrounded
        ln32 = () if normed else (ln_w.astype(f32), ln_b.astype(f32))

        def body(carry, xl):
            nll_sum, cnt, dw, dln, dextra = carry
            xch, lch, *wch = xl
            # the norm takes and gives float32 here (what it computes in):
            # its vjp then takes dxn as the matmul gave it. dx is rounded to
            # x's dtype twice, stacked and after the backward rule's scaling,
            # where autodiff rounds dxn and then dx
            if normed:
                xn = xch
            else:
                xn32, norm_vjp = jax.vjp(norm, xch.astype(f32), *ln32)
                xn = xn32.astype(xch.dtype)
            logits = _head_logits(xn, w, extra, divisor)
            with trace.scope("head_softmax"):
                mask = lch >= 0
                label = jnp.where(mask, lch, 0)[..., None]
                shifted = logits - logits.max(axis=-1, keepdims=True)
                e = jnp.exp(shifted)
                total = e.sum(axis=-1, keepdims=True)
                nll = (jnp.log(total) - jnp.take_along_axis(shifted, label, axis=-1)
                       )[..., 0]
                hot = jnp.arange(logits.shape[-1]) == label
                dlogits = jnp.where(mask[..., None], e / total - hot, 0.0)
                if weighed:
                    dlogits = dlogits * wch[0][..., None]
                if divisor != 1.0:
                    # of the undivided logits, the matmul's own output
                    dlogits = dlogits / divisor
                # the MXU's operand in both matmuls below: what a
                # default-precision matmul makes of the float32 cotangent on
                # the chip. Behind a barrier, so that it is written once: XLA
                # otherwise fuses this block into each matmul's operand,
                # which then re-reads the float32 logits and takes exp again
                # (olmoe-train: dw 30.7 -> 20.1 ms a step, dx 20.9 -> 17.9;
                # PERF.md PR 32)
                dl = jax.lax.optimization_barrier(dlogits.astype(xn.dtype))
            with trace.scope("head_dx"):
                dxn = jax.lax.dot_general(dl, w, (((2,), (1,)), ((), ())),
                                          preferred_element_type=f32)
                dx, *dln_chunk = (dxn,) if normed else norm_vjp(dxn)
            with trace.scope("head_dw"):
                dw = dw + jax.lax.dot_general(xn, dl, (((0, 1), (0, 1)), ((), ())),
                                              preferred_element_type=f32)
                dln = tuple(a + b for a, b in zip(dln, dln_chunk))
                if biased:
                    dextra = dextra + dlogits.sum(axis=(0, 1))
            nll = nll * mask
            return ((nll_sum + (nll * wch[0] if weighed else nll).sum(), cnt + mask.sum(),
                     dw, dln, dextra),
                    (dx.astype(xch.dtype), nll) if weighed else dx.astype(xch.dtype))

        zeros = lambda a: jnp.zeros(a.shape, f32)
        (nll_sum, cnt, dw, dln, dextra), out = jax.lax.scan(
            body, (jnp.zeros((), f32), jnp.zeros((), jnp.int32), zeros(w),
                   tuple(zeros(a) for a in ln32), zeros(extra) if biased else None),
            (xc, lc, wc) if weighed else (xc, lc))
        dxc, rows = out if weighed else (out, None)
        return ((nll_sum, cnt, rows) if weighed else (nll_sum, cnt),
                (dxc, dw, dln, dextra, ln_w, ln_b, rows))

    def bwd(res, cotangents):
        g = cotangents[0]               # of nll_sum; the count's is float0
        dxc, dw, dln, dextra, ln_w, ln_b, rows = res
        scaled = lambda a, dtype: (a.astype(f32) * g).astype(dtype)
        return (None if normed else scaled(dln[0], ln_w.dtype),
                None if normed else scaled(dln[1], ln_b.dtype), None,
                scaled(dw, f32), scaled(dextra, f32) if biased else None,
                scaled(dxc, dxc.dtype), None,
                # d (sum of weight x nll) / d weight: the rows' own losses
                scaled(rows, f32) if weighed else None)

    scan = jax.custom_vjp(primal)
    scan.defvjp(fwd, bwd)
    out = scan(ln_w, ln_b, w, w_acc, extra, xc, lc, wc)
    return (*out[:2], jax.lax.stop_gradient(out[2])) if weighed else out


def _keeping_splash_residuals(policy):
    """``policy`` (of ``_remat_policy``) for a mixer half under per-half
    remat: what ``policy`` keeps (or offloads) and the splash attention
    kernels' own residuals ``out`` and ``logsumexp`` (``ops/flash_attention
    .SPLASH_RESIDUALS``, named inside the kernel's forward rule), so that the
    half's replay enters the backward kernels from saved state. A half
    without a splash kernel holds no such name and keeps what ``policy``
    keeps. ``None`` ("none") stays ``None``."""
    from ..ops.flash_attention import SPLASH_RESIDUALS

    if policy is None:
        return None

    # (not ``save_from_both_policies``: it takes policies that answer with a
    # bool, and "offload_kv_host" answers Recompute / Offloadable)
    def both(prim, *avals, **params):
        if prim.name == "name" and params["name"] == SPLASH_RESIDUALS:
            return True
        return policy(prim, *avals, **params)

    return both


def _remat_policy(name: str):
    import jax

    policies = {
        # no policy: ``jax.checkpoint``'s default keeps NOTHING, the splash
        # kernels' residuals included (``_keeping_splash_residuals`` leaves
        # None alone): the least a remat'ed layer can hold. "full" and
        # "nothing_saveable" are that for the ``attn`` family and ``gdn``;
        # an ``mla`` / ``gated_attn`` half keeps the residuals under them
        "none": None,
        "full": jax.checkpoint_policies.nothing_saveable,
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "dots_with_no_batch_dims_saveable": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # FPDT host offload (reference fpdt_layer.py:462,971): per-layer KV
        # lives in host RAM between fwd and bwd instead of HBM; everything
        # else recomputes. Max context becomes host-RAM-bound, not HBM-bound.
        "offload_kv_host": jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[], names_which_can_be_offloaded=["kv"],
            offload_src="device", offload_dst="pinned_host"),
        # Selective saves between the nothing_saveable / dots_saveable
        # extremes ([B,T,*]-sized named seams only, never the full dots set):
        # "save_attn_seams" keeps q/kv/attn (skips the attention-side
        # recompute in backward, ~1/6 of layer FLOPs at seq 4k);
        # "save_ffn" also keeps the two big FFN projections (skips ~80% of
        # the backward recompute; costs 2*T*d_ff bf16 per layer).
        "save_attn_seams": jax.checkpoint_policies.save_only_these_names(
            "q", "kv", "attn"),
        "save_ffn": jax.checkpoint_policies.save_only_these_names(
            "q", "kv", "attn", "ffn_gate", "ffn_up"),
        # Save the flash kernel's OWN residuals (out + logsumexp, named
        # inside ops/alibi_attention._alibi_flash_fwd_impl) so backward
        # enters the flash bwd kernels directly from saved state — the
        # forward attention kernel is DCE'd out of the remat recompute.
        # Why "save_attn_seams" lost ~1pt despite saving "attn": the layer-
        # level attn seam is NOT a residual of the kernel's custom vjp —
        # the backward replay still re-ran the flash forward to rebuild its
        # (out, lse) residuals, so that policy paid the HBM for the saved
        # seams without removing any attention recompute. Saving the
        # residuals themselves (this policy) is what removes it; cost is
        # out[B,T,H,D] bf16 + lse[B,H,T] f32 per layer. Requires the model
        # to route attention through the lse kernel (Transformer._attention
        # does this automatically under this policy). The ``attn`` family's
        # route (one checkpoint a layer, head_dim 64/128). The pattern's own
        # mixers (``mla``, ``gated_attn``: per-half remat, splash kernels)
        # reach the same end under EVERY policy here but "none" (the
        # offloading one too: its answers are not booleans), with no policy
        # of their own: ``_keeping_splash_residuals`` adds the splash
        # kernels' residual name to the mixer half's policy.
        "save_flash_lse": jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse"),
    }
    return policies.get(name, jax.checkpoint_policies.dots_saveable)
