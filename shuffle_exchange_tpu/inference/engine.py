"""Inference engine v1 — jit-compiled serving over a dense KV cache.

Capability analog of the reference ``InferenceEngine`` (``inference/engine.py:40``):
wrap a model + weights, apply the TP sharding policy (the AutoTP /
kernel-injection analog is the model's partition specs + Pallas attention),
and serve ``forward``/``generate``. Where the reference captures CUDA graphs
(``inference/engine.py:494``) we jit one prefill program per (batch, length)
bucket and one decode program — XLA's equivalent of graph replay.

Design (TPU-first):
  - KV cache is a pair of stacked arrays [L, B, S, KV, Dh] scanned alongside
    the stacked layer weights — O(1) compile in depth.
  - The whole generate loop (prefill -> lax.scan of decode steps with fused
    on-device sampling) is ONE jitted program: no host round-trip per token
    (the reference's decode loop re-enters python per token).
  - Right-padded prompts with per-sequence lengths; positions/RoPE are
    per-sequence gathers.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from ..profiling import trace
from ..utils.logging import log_dist, logger
from .config import InferenceConfig
from . import sampling


class KVCache(NamedTuple):
    k: Any  # [L, B, S, KV, Dh]
    v: Any


def _bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def _rope_rows(cos, sin, pos):
    """Gather per-sequence rope rows. pos [B] or [B,T] -> cos/sin [B,T,D/2]."""
    import jax.numpy as jnp

    if pos.ndim == 1:
        pos = pos[:, None]
    return jnp.take(cos, pos, axis=0), jnp.take(sin, pos, axis=0)


def _apply_rope_batched(x, cos, sin, interleaved: bool = False):
    """x [B,T,H,D], cos/sin [B,T,rd/2] (per-sequence positions); partial
    rotary dims pass through, pairing per ``interleaved`` (see
    models/transformer.py apply_rope)."""
    import jax.numpy as jnp

    rd = 2 * cos.shape[-1]
    rot, rest = (x[..., :rd], x[..., rd:]) if rd < x.shape[-1] else (x, None)
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    if interleaved:
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(rot.shape)
    else:
        x1, x2 = jnp.split(rot, 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out if rest is None else jnp.concatenate([out, rest], axis=-1)


def decode_attention(q, ck, cv, kv_len, alibi_slopes=None):
    """Single-token attention against a cache.

    q [B,1,H,Dh], ck/cv [B,S,KV,Dh], kv_len [B] = #valid cache slots.
    fp32 softmax; GQA via head-group reshape (no materialized repeat).
    ``alibi_slopes`` [H]: ALiBi bias slope_h * j at key slot j (BLOOM).
    Reference: v1 softmax_context kernel (ops/transformer/inference/op_binding/
    softmax_context.py) and v2 blocked_flash decode path.
    """
    import jax.numpy as jnp

    B, S, KV, Dh = ck.shape
    H = q.shape[2]
    G = H // KV
    # Operands stay in cache dtype with fp32 ACCUMULATION — an
    # astype(float32) on ck/cv would materialize a fp32 copy of the whole
    # cache per layer per token (~2x the decode HBM traffic); softmax runs
    # on the fp32 scores either way.
    qf = q.astype(ck.dtype).reshape(B, KV, G, Dh)              # T=1 folded away
    scores = jnp.einsum("bkgd,bskd->bkgs", qf, ck,
                        preferred_element_type=jnp.float32) / np.sqrt(Dh)
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(KV, G)
        scores = scores + slopes[None, :, :, None] * jnp.arange(S, dtype=jnp.float32)
    mask = (jnp.arange(S)[None, :] < kv_len[:, None])[:, None, None, :]
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    w = jnp.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", w.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def extend_attention(q, ck, cv, start_pos, kv_len, alibi_slopes=None):
    """Chunked-prefill attention: a C-token query chunk against the cache.

    q [B,C,H,Dh]; ck/cv [B,S,KV,Dh] already contain the chunk's own K/V at
    positions start_pos..start_pos+C-1; start_pos/kv_len [B]. Query i may see
    cache slots s with s <= start_pos + i and s < kv_len (causal within the
    chunk, full visibility of the prefix). fp32 softmax.
    Reference: the ragged "atom" attention over mixed prefill+decode
    (inference/v2/kernels/ragged_ops/blocked_flash) — decode is C == 1.
    """
    import jax.numpy as jnp

    B, S, KV, Dh = ck.shape
    C, H = q.shape[1], q.shape[2]
    G = H // KV
    # Same fp32-accumulate / no-cache-cast discipline as decode_attention.
    qf = q.astype(ck.dtype).reshape(B, C, KV, G, Dh)
    scores = jnp.einsum("bckgd,bskd->bckgs", qf, ck,
                        preferred_element_type=jnp.float32) / np.sqrt(Dh)
    if alibi_slopes is not None:
        slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(KV, G)
        scores = scores + slopes[None, None, :, :, None] * jnp.arange(S, dtype=jnp.float32)
    s_idx = jnp.arange(S)[None, None, :]
    lim = jnp.minimum(start_pos[:, None] + jnp.arange(C)[None, :] + 1, kv_len[:, None])
    mask = (s_idx < lim[:, :, None])[:, :, None, None, :]
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    w = jnp.exp(scores - scores.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    out = jnp.einsum("bckgs,bskd->bckgd", w.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, H, Dh).astype(q.dtype)


class InferenceEngine:
    """Serve a model: ``forward(ids)`` and ``generate(ids, prompt_lengths)``.

    ``model`` is our Transformer family (models/transformer.py); ``params``
    its pytree (cast to the serving dtype and TP-sharded on construction).
    """

    # v2 overrides: its paged decode step can fuse ATTENTION (split-K paged
    # kernel + in-pool append) even when qkv/mlp fusion is structurally off
    _fused_attention = False
    # v2 overrides: only the paged engine runs speculative verify rows, so
    # only it gets the verify-width routing gate/warning (a v1 engine built
    # from a speculative-enabled config has no verify lane to route)
    _has_verify_lane = False

    def __init__(self, model, params, config: Optional[InferenceConfig] = None):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.config = config or InferenceConfig()
        self._mcfg = model.config
        mixers = {mixer for mixer, _ in getattr(self._mcfg, "kinds_used", ())}
        if "kda" in mixers:
            raise NotImplementedError(
                "serving a delta rule with a decay a key channel (mixer 'kda': Kimi "
                "Delta Attention) beside latent attention that rotates nothing is "
                "not implemented: a layer's state is a [dk, dv] matrix a head and "
                "three convolution tails, which no cache or scheduler holds, the "
                "rule has no one-token step here, and the latent layers would need "
                "a latent cache WITHOUT rotated keys (training through "
                "sxt.initialize is; ROADMAP R-M5, R-M4)")
        if (getattr(self._mcfg, "moe_router_input", "ffn") != "ffn"
                or getattr(self._mcfg, "unrotated_mixers", ())):
            raise NotImplementedError(
                "serving a stack whose router reads the block's input "
                "(moe_router_input 'block') or whose full layers rotate nothing "
                "beside window layers that do (unrotated_mixers: SmallThinker) is "
                "not implemented: the inference engines' one-kind decode scan "
                "routes on what the experts read, rotates every layer by one "
                "table and keeps one uniform KV pool with no window to evict by; "
                "nothing fetches experts while attention runs (training through "
                "sxt.initialize is; ROADMAP R-M3, R-M15)")
        if getattr(self._mcfg, "loop_steps", 1) > 1:
            raise NotImplementedError(
                f"serving a looped stack (loop_steps {self._mcfg.loop_steps}: the layers "
                "run several times over the same weights, an exit gate a token) is "
                "not implemented: inference/paged.py keeps one KV block a layer, "
                "not one a (loop step, layer), and no decode step stops some rows "
                "early by their cumulated exit mass (training through "
                "sxt.initialize is; ROADMAP R-M17)")
        if "dsa" in mixers:
            raise NotImplementedError(
                "serving a learned sparse attention (mixer 'dsa': an indexer that "
                "scores every cached key and a top-k selection a query, KeyeVL2's "
                "sa_config) is not implemented: inference/paged.py keeps no "
                "indexer key beside a KV block and paged attention has no "
                "selection inside it (training through sxt.initialize is; "
                "ROADMAP R-M16)")
        if "swa" in mixers:
            raise NotImplementedError(
                "serving a stack of window and full attention kinds (mixer "
                "'swa' beside 'attn': layer_pattern) with per-kind head counts "
                "and RoPE tables is not implemented: the inference engines "
                "keep ONE uniform KV pool (one head count, one table, every "
                "key kept) for one kind of layer, with no window to evict by "
                "(training through sxt.initialize is; ROADMAP R-M3)")
        if "sconv" in mixers:
            raise NotImplementedError(
                "serving a stack with gated short-convolution layers (mixer "
                "'sconv' beside 'attn': layer_pattern, LFM2) is not "
                "implemented: a convolution layer carries the last "
                "sconv_taps - 1 rows of its gated input as its state, a tail "
                "the inference engines hold beside no KV block, and their "
                "attention has no per-head q/k norm (training through "
                "sxt.initialize is; ROADMAP R-M10)")
        ffns = {ffn for _, ffn in getattr(self._mcfg, "kinds_used", ())}
        if "ssm" in mixers or "none" in ffns:
            raise NotImplementedError(
                "serving a stack with Mamba-2 state-space layers (mixer 'ssm') "
                "or layers that are a mixer alone (ffn 'none': layer_pattern, "
                "Nemotron-H) is not implemented: a state-space layer carries "
                "the last ssm_conv_kernel - 1 rows of its convolution's input "
                "and a [head_dim, state] matrix a head as its state, which the "
                "inference engines hold beside no KV block, they have no "
                "one-token step of the scan, and they scan whole (mixer, ffn) "
                "blocks of one kind (training through sxt.initialize is; "
                "ROADMAP R-M11)")
        scaled = [f"{name}={getattr(self._mcfg, name)}"
                  for name in ("embed_scale", "residual_scale", "logit_divisor")
                  if getattr(self._mcfg, name, 1.0) != 1.0]
        if scaled:
            raise NotImplementedError(
                f"serving a model with the Granite family's multipliers ({', '.join(scaled)}"
                ": embedding_multiplier, residual_multiplier, logits_scaling) is "
                "not implemented: the inference engines' cached paths embed, add "
                "sublayers and read logits unscaled (training through "
                "sxt.initialize is; ROADMAP R-M11)")
        if getattr(self._mcfg, "norm_order", "input") != "input":
            raise NotImplementedError(
                "serving the Olmo Hybrid family (model_type olmo_hybrid: blocks "
                "that norm each sublayer's OUTPUT, Gated DeltaNet layers whose "
                "write strength is 2 sigmoid beside full attention under a "
                "whole-projection q/k norm; norm_order 'output') is not "
                "implemented: the inference engines' cached paths norm a "
                "block's input, keep one kind of state, a KV cache, for one "
                "kind of layer, and have no one-token step of the delta rule "
                "(training through sxt.initialize is; ROADMAP R-M12)")
        if getattr(self._mcfg, "recurrent", False) or len(
                getattr(self._mcfg, "pattern", ((),))) > 1:
            # the cached paths scan ONE kind of layer over a KV cache: a
            # recurrent mixer (Gated DeltaNet) carries a convolution tail and
            # a [dk, dv] state per head instead, which neither the caches nor
            # the schedulers hold
            raise NotImplementedError(
                "serving a stack with a recurrent mixer (Gated DeltaNet "
                "layers beside full-attention layers: layer_pattern) is not "
                "implemented: the inference engines keep one kind of state, a "
                "KV cache, for one kind of layer (training through "
                "sxt.initialize is; ROADMAP R-M5)")
        if getattr(self._mcfg, "latent", False) or getattr(self._mcfg, "lead_layers", 0):
            raise NotImplementedError(
                "serving a model with latent attention (MLA: mixer 'mla', "
                "DeepSeek-V3 / kanana-2) is not implemented: the engines have "
                "no latent paged cache (one latent and one rotary key a token "
                "instead of k and v), no prefill over it and no absorbed "
                "decode path, and they scan ONE kind of layer (this stack "
                "starts with leading layers of another) (training through "
                "sxt.initialize is; ROADMAP R-M4)")
        if getattr(self._mcfg, "experts_held", 0) != getattr(self._mcfg, "n_experts", 0):
            raise NotImplementedError(
                "serving one expert-parallel rank's share of the experts "
                "(n_experts_held) alone is not implemented: the tokens a share "
                "emits are not the model's (training a share through "
                "sxt.initialize is; ROADMAP R-M2)")
        if getattr(self._mcfg, "qk_norm", False):
            # the cached decode paths project q and k without the whole-
            # projection RMSNorm: serving such a model would be silently wrong
            raise NotImplementedError(
                "serving a model with q/k RMSNorm (OLMoE's over the whole "
                "projection, LFM2's per head) is not implemented "
                "yet: the inference engines' attention has no q/k norm "
                "(training through sxt.initialize is; ROADMAP R1)")
        if self._mcfg.position == "alibi":
            from ..models.transformer import alibi_slopes

            self._alibi = (alibi_slopes(self._mcfg.n_heads)
                           * self._mcfg.alibi_slope_scale)
        else:
            self._alibi = None
        self._gen_cache: Dict[Tuple, Any] = {}
        self._fwd = jax.jit(model.apply)
        self._rng = jax.random.PRNGKey(self.config.seed)
        self._resolve_decode_kernel()
        self.update_params(params)

    def _resolve_decode_kernel(self) -> None:
        """Pin the decode-path implementation for this engine's lifetime
        (the jitted programs bake it in). "auto" falls back to the XLA
        layer body off-TPU or when the model structure isn't fusable;
        "pallas" raises instead of silently degrading."""
        from ..models.transformer import decode_fusion_eligibility
        from ..ops.dispatch import resolve_decode_kernel
        from ..utils.logging import warning_once

        requested = self.config.decode_kernel
        # speculative verify width (ISSUE 8): k+1-token verify rows are
        # outside the single-token fused decode kernels' contract — the
        # resolver warns once and the eligibility dict records the gate,
        # so the routing is explicit instead of shape-dependent
        spec = self.config.serving.speculative
        spec_k = spec.k if (spec.enabled and self._has_verify_lane) else 0
        self._decode_kernel = resolve_decode_kernel(requested,
                                                    speculative_k=spec_k)
        self._fuse_qkv = self._fuse_mlp = False
        if self._decode_kernel != "pallas":
            return
        elig = decode_fusion_eligibility(self._mcfg, speculative_k=spec_k)
        self._fuse_qkv = elig["qkv"] is None
        self._fuse_mlp = elig["mlp"] is None
        reasons = [r for r in (elig["qkv"], elig["mlp"]) if r]
        if not (self._fuse_qkv or self._fuse_mlp or self._fused_attention):
            if requested == "pallas":
                raise ValueError(
                    "decode_kernel='pallas' but no part of the decode "
                    f"layer is fusable for this model: {'; '.join(reasons)}")
            # sxt: ignore[SXT005] reasons derive from the model config, fixed per process — dedup cardinality 1
            warning_once(f"decode_kernel=auto: model not fusable "
                         f"({'; '.join(reasons)}); using the XLA decode path")
            self._decode_kernel = "xla"
        elif reasons:
            # sxt: ignore[SXT005] reasons derive from the model config, fixed per process — dedup cardinality 1
            warning_once("fused decode: partially fused layer body "
                         f"({'; '.join(reasons)})")

    def _prepare_params(self, params):
        """Cast to the serving dtype, quantize when configured, and place —
        everything ``update_params`` does short of the commit. Split out so
        the RLHF weight-publication path (``rlhf/publish.py``) can STAGE a
        prepared tree per replica and flip every replica's pointer only
        after all of them prepared successfully (two-phase publish: the
        prepare is the phase that can fail, the commit is a pointer swap)."""
        import jax
        import jax.numpy as jnp

        from ..ops.quant_matmul import QuantizedMatrix

        dtype = self.config.jax_dtype()
        params = jax.tree.map(
            lambda p: p.astype(dtype) if (not isinstance(p, QuantizedMatrix)
                                          and hasattr(p, "astype")
                                          and jnp.issubdtype(p.dtype, jnp.floating)) else p,
            params, is_leaf=lambda p: isinstance(p, QuantizedMatrix))
        if self.config.quantize_weights:
            params = self._quantize(params)
        return self._place(params)

    def update_params(self, params) -> None:
        """Swap in new weights (same tree/shapes) without dropping compiled
        programs — the hybrid-engine path (reference hybrid_engine.py swaps
        inference containers in during ``generate()``; here the jitted
        generate/prefill/decode programs are weight-agnostic, so refreshing
        the pytree is the whole swap)."""
        self.params = self._prepare_params(params)

    # -- checkpoint-backed serving (resilience layer) -------------------

    @classmethod
    def from_checkpoint(cls, model, ckpt_dir: str,
                        config: Optional[InferenceConfig] = None,
                        tag: Optional[str] = None) -> "InferenceEngine":
        """Serve straight from a training checkpoint directory, with the
        same torn-latest / corrupted-tag fallback as the trainer (see
        ``load_serving_weights``). Works for every engine class (v2
        inherits)."""
        return cls(model, load_serving_weights(ckpt_dir, model, tag=tag), config)

    def _try_load_serving_weights(self, ckpt_dir: str,
                                  tag: Optional[str] = None):
        """``load_serving_weights`` with the reload-path degrade policy:
        when no tag is loadable — mid-save, torn ``latest``, corrupted
        shards — log and return None so the caller KEEPS SERVING its
        current weights (shared by both reload_weights overloads; the
        exception set and message live in exactly one place)."""
        try:
            return load_serving_weights(ckpt_dir, self.model, tag=tag)
        except (ValueError, OSError) as e:
            logger.warning(f"reload_weights: no loadable checkpoint in "
                           f"{ckpt_dir} ({type(e).__name__}: {e}); continuing "
                           "to serve the current weights")
            return None

    def reload_weights(self, ckpt_dir: str, tag: Optional[str] = None) -> bool:
        """Hot-swap serving weights from the newest complete checkpoint in
        ``ckpt_dir`` (a serving fleet following a live trainer). Degrades
        gracefully (see ``_try_load_serving_weights``): an unloadable
        directory returns False and keeps serving."""
        params = self._try_load_serving_weights(ckpt_dir, tag=tag)
        if params is None:
            return False
        self.update_params(params)
        return True

    # -- sharding (AutoTP analog: inference/engine.py:247 TP group create) --

    def _place(self, params):
        import jax

        from ..parallel.mesh import get_topology, topology_is_initialized

        if not topology_is_initialized():
            return jax.device_put(params)
        from ..ops.quant_matmul import QuantizedMatrix

        topo = get_topology()
        if topo.size("tensor") == 1 or not hasattr(self.model, "partition_specs"):
            return jax.device_put(params)
        specs = self.model.partition_specs(params)

        def place(p, spec):
            if isinstance(p, QuantizedMatrix):
                # TP-sharding the int8 storage needs scale-aware specs;
                # replicate for now (quantized serving is single-chip-first)
                return jax.device_put(p)
            # replicate any leaf a mesh axis doesn't divide (odd vocab or
            # head counts must degrade, not crash serving)
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                size = 1
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    size *= topo.size(a)
                if p.shape[dim] % size:
                    return jax.device_put(p)
            return jax.device_put(p, topo.named_sharding(*spec))

        return jax.tree.map(place, params, specs,
                            is_leaf=lambda p: isinstance(p, QuantizedMatrix))

    def _quantize(self, params):
        """int8 weight-only quantization (reference GroupQuantizer
        ``module_inject/replace_module.py:44`` + the mixed_gemm CUTLASS
        kernels, SURVEY §2.13). Layer matmul weights become int8-STORAGE
        :class:`QuantizedMatrix` leaves — half the HBM bytes; `y @ w`
        dequantizes into the dot (XLA fuses the convert, so weights cross
        HBM quantized — measured faster than the Pallas quant kernel at
        every serving shape, round 5: int8 generate 930 vs 612 tok/s).
        int8/fp8 MoE expert weights also take storage form (the grouped
        GEMM / batched-einsum paths dequantize into the dot); int4 MoE
        and unembed (fp32 head path) keep the rounding-only emulation."""
        import jax

        from ..ops.quant import quantize_dequantize
        from ..ops.quant_matmul import quantize_weight

        from ..utils.logging import warning_once

        gs = self.config.quant_group_size
        # storage weights group along K with one scale row per kernel
        # K-block; 256 is the largest MXU-friendly group (see
        # InferenceConfig.quant_group_size docs) — larger configured values
        # apply to the moe/unembed rounding path only
        storage_gs = min(gs, 256)
        storage_names = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
        moe_names = {"moe_w_gate", "moe_w_up", "moe_w_down"}
        if self.config.quant_bits in (8, "fp8"):
            # expert-sharded MoE FFN weights join int8/fp8 STORAGE (ISSUE
            # 20 satellite): quantize_weight groups along K under the
            # stacked [L, E] lead dims, and both expert compute paths
            # dequantize into the dot (batched einsum in expert_mlp,
            # grouped_matmul's ragged_dot/gmm dispatch) — so expert
            # weights cross HBM at quantized width during streamed
            # decode, same contract as the dense w_* leaves. int4 keeps
            # the rounding emulation: its nibble-pair unpack is plumbed
            # for the 2D serving matmul only.
            storage_names = storage_names | moe_names
            qdq_names = {"unembed"}
        else:
            qdq_names = moe_names | {"unembed"}
        dtype = self.config.jax_dtype()

        def walk(tree):
            if isinstance(tree, dict):
                out = {}
                for k, v in tree.items():
                    if k in storage_names:
                        try:
                            out[k] = quantize_weight(v, group_size=storage_gs, dtype=dtype,
                                                      bits=self.config.quant_bits)
                        except ValueError as e:
                            # static message: this loop visits every weight,
                            # and a per-weight f-string would defeat the
                            # warning_once dedup (one line per leaf)
                            warning_once(
                                "quantize_weight rejected some weights; "
                                "using quantize-dequantize rounding for "
                                "them (per-weight detail at debug level)")
                            logger.debug(f"quantize_weight({k}): {e}; "
                                         f"qdq rounding instead")
                            out[k] = quantize_dequantize(v, group_size=gs).astype(v.dtype)
                    elif k in qdq_names:
                        out[k] = quantize_dequantize(v, group_size=gs).astype(v.dtype)
                    else:
                        out[k] = walk(v)
                return out
            return tree

        return walk(params)

    # -- cached forward pieces ----------------------------------------

    def _embed_at(self, params, ids, pos):
        """ids [B,T], pos [B] start positions -> x [B,T,D], plus rope tables."""
        import jax.numpy as jnp

        from ..models.transformer import rope_table

        cfg = self._mcfg
        x = jnp.take(params["embed"], ids, axis=0)
        if cfg.embed_ln:   # BLOOM word_embeddings_layernorm
            from ..models.transformer import _norm

            x = _norm(x, params["embed_ln_w"], params["embed_ln_b"], cfg.norm,
                      eps=cfg.norm_eps)
        T = ids.shape[1]
        positions = pos[:, None] + jnp.arange(T)[None, :]       # [B,T]
        if cfg.position == "learned":
            # "clip" keeps an out-of-range position (generation running past
            # max_seq_len) pinned to the last row instead of silently
            # wrapping via the default fill behavior.
            x = x + jnp.take(params["pos_embed"], positions + cfg.pos_offset,
                             axis=0, mode="clip").astype(x.dtype)
            return x, (None, None), positions
        if cfg.position == "alibi":
            return x, (None, None), positions
        # the model's own table (YaRN where it states one); a stack with a
        # second table (mixer "swa") is refused in __init__
        cos, sin = rope_table(self.config.max_seq_len, cfg.rotary_dims, cfg.rope_theta,
                              cfg.rope_yarn)
        return x, (cos, sin), positions

    def _lora_add(self, base, x, lora, target):
        """``base + (x @ A_slot[row]) @ B_slot[row]`` — the per-row paged
        adapter delta (ISSUE 18). ``lora`` is ``(pool_slice, slots)``:
        the layer's [S, din, R]/[S, R, dout] factor stacks and the
        batch's i32 slot indices (slot 0 = zeros, an exact no-op)."""
        pool, slots = lora
        if target not in pool["a"]:
            return base
        from ..ops.lora_gemm import lora_delta

        delta = lora_delta(x, pool["a"][target], pool["b"][target], slots)
        return base + delta.astype(base.dtype)

    def _layer_body(self, lw, h, cos, sin, positions, attn_fn, lora=None):
        """One transformer block shared by every cached path (v1/v2 ×
        prefill/decode) — norm → QKV(+RoPE) → ``attn_fn`` → residual → FFN.
        ``attn_fn(q, k, v) -> (attn [B,T,H,Dh], cache_out)`` supplies the
        attention and the KV-cache write for that path.

        On 1-token steps with ``decode_kernel`` resolved to "pallas", the
        QKV projection(+bias+RoPE) and the residual+MLP collapse into the
        fused kernels (ops/fused_decode.py) so each weight matrix streams
        through VMEM exactly once per step.

        ``lora`` (ISSUE 18) threads the adapter pool's per-layer factor
        stacks + the batch's slot indices; the low-rank delta lands on
        each projection AFTER the base matmul and BEFORE bias/RoPE (the
        fused-QKV collapse is statically skipped — the engine only
        passes ``lora`` when adapters are enabled)."""
        from ..models.transformer import _norm

        cfg = self._mcfg
        B, T = h.shape[:2]
        H, KV, Dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        with trace.scope("attn_norm"):
            y = _norm(h, lw["ln1_w"], lw.get("ln1_b", 0), cfg.norm, eps=cfg.norm_eps)
        with trace.scope("attn_qkv"):
            qkv = None if lora is not None else \
                self._maybe_fused_qkv(lw, y, cos, sin, positions)
            if qkv is None:
                q = y @ lw["wq"]
                k = y @ lw["wk"]
                v = y @ lw["wv"]
                if lora is not None:
                    q = self._lora_add(q, y, lora, "wq")
                    k = self._lora_add(k, y, lora, "wk")
                    v = self._lora_add(v, y, lora, "wv")
                q = q.reshape(B, T, H, Dh)
                k = k.reshape(B, T, KV, Dh)
                v = v.reshape(B, T, KV, Dh)
                if cfg.attn_qkv_bias:
                    q = q + lw["b_q"].astype(y.dtype).reshape(H, Dh)
                    k = k + lw["b_k"].astype(y.dtype).reshape(KV, Dh)
                    v = v + lw["b_v"].astype(y.dtype).reshape(KV, Dh)
                if cfg.position == "rope":
                    pc, ps = _rope_rows(cos, sin, positions)
                    q = _apply_rope_batched(q, pc, ps, interleaved=cfg.rope_interleaved)
                    k = _apply_rope_batched(k, pc, ps, interleaved=cfg.rope_interleaved)
            else:
                q, k, v = qkv
        with trace.scope("attn_core"):      # attention and the KV-cache write
            attn, cache_out = attn_fn(q, k, v)
        return self._block_tail(lw, h, y, attn, lora=lora), cache_out

    def _block_tail(self, lw, h, y, attn, lora=None):
        """Output projection + residual(s) + FFN — shared by the XLA and
        fused layer bodies (engine_v2's fused paged step re-enters here
        after its fused attention)."""
        cfg = self._mcfg
        B, T = h.shape[:2]
        with trace.scope("attn_out"):
            attn_flat = attn.reshape(B, T, cfg.n_heads * cfg.head_dim)
            attn_out = attn_flat @ lw["wo"]
            if lora is not None:
                attn_out = self._lora_add(attn_out, attn_flat, lora, "wo")
            if cfg.attn_out_bias:
                attn_out = attn_out + lw["b_o"].astype(attn_out.dtype)
        # the fused MLP kernel holds its norm, so the second half is one scope
        with trace.scope("moe" if cfg.n_experts > 0 else "mlp"):
            return self._ffn_tail(lw, h, y, attn_out)

    def _ffn_tail(self, lw, h, y, attn_out):
        from ..models.transformer import _norm

        cfg = self._mcfg
        if cfg.parallel_block:
            resid = h + attn_out
            if cfg.parallel_shared_ln:
                out = self._maybe_fused_ffn(lw, resid, y, apply_norm=False)
                return out if out is not None else resid + self._ffn(lw, y)
            out = self._maybe_fused_ffn(lw, resid, h, apply_norm=True)
            if out is not None:
                return out
            with trace.scope("mlp_norm"):
                y2 = _norm(h, lw["ln2_w"], lw.get("ln2_b", 0), cfg.norm,
                           eps=cfg.norm_eps)
            return resid + self._ffn(lw, y2)
        h = h + attn_out
        out = self._maybe_fused_ffn(lw, h, h, apply_norm=True)
        if out is not None:
            return out
        with trace.scope("mlp_norm"):
            y2 = _norm(h, lw["ln2_w"], lw.get("ln2_b", 0), cfg.norm, eps=cfg.norm_eps)
        return h + self._ffn(lw, y2)

    def _fused_qkv_args(self, lw, cos, sin, positions):
        """Per-layer preconditions + argument assembly shared by the v1
        and v2 fused-QKV call sites (one definition so weight-form checks
        can never diverge between the engines): None when this layer's
        attention weights can't take the kernel, else
        ``(cos_rows, sin_rows, bias_kwargs)``."""
        cfg = self._mcfg
        from ..ops.quant_matmul import QuantizedMatrix
        from ..utils.logging import warning_once

        if any(isinstance(lw[n], QuantizedMatrix) for n in ("wq", "wk", "wv")):
            warning_once("fused decode: quantized attention weights — QKV "
                         "stays on the dequant-into-dot XLA path")
            return None
        cosr = sinr = None
        if cfg.position == "rope":
            pc, ps = _rope_rows(cos, sin, positions)
            cosr, sinr = pc[:, 0], ps[:, 0]
        bias = {}
        if cfg.attn_qkv_bias:
            bias = {"bq": lw["b_q"], "bk": lw["b_k"], "bv": lw["b_v"]}
        return cosr, sinr, bias

    def _maybe_fused_qkv(self, lw, y, cos, sin, positions):
        """Fused QKV+bias+RoPE for a 1-token step; None -> use the XLA
        path (not enabled, T > 1, or this layer's weights aren't dense).
        A kernel that was selected runs or raises."""
        cfg = self._mcfg
        if not (self._fuse_qkv and self._decode_kernel == "pallas"
                and y.shape[1] == 1):
            return None
        from ..ops import fused_decode as fd

        args = self._fused_qkv_args(lw, cos, sin, positions)
        if args is None:
            return None
        cosr, sinr, bias = args
        q, k, v = fd.fused_qkv_rope(
            y[:, 0], lw["wq"], lw["wk"], lw["wv"], cos=cosr, sin=sinr,
            n_heads=cfg.n_heads, kv_heads=cfg.kv_heads, **bias)
        return q[:, None], k[:, None], v[:, None]

    def _maybe_fused_ffn(self, lw, resid, y_src, apply_norm: bool):
        """Fused residual+norm+MLP for a 1-token step; None -> XLA path."""
        cfg = self._mcfg
        if not (self._fuse_mlp and self._decode_kernel == "pallas"
                and resid.shape[1] == 1):
            return None
        from ..ops import fused_decode as fd
        from ..ops.quant_matmul import QuantizedMatrix
        from ..utils.logging import warning_once

        gated = cfg.activation == "swiglu"
        wg = lw["w_gate"] if gated else None
        reason = fd.mlp_weights_fusable(lw["w_up"], lw["w_down"], wg)
        has_bias = cfg.mlp_bias and not gated and "b_up" in lw
        if reason is None and has_bias and isinstance(lw["w_up"],
                                                      QuantizedMatrix):
            reason = "quantized MLP weights with fc biases"
        if reason is not None:
            # sxt: ignore[SXT005] reason derives from the weight structure, fixed per process
            warning_once(f"fused decode: MLP stays on the XLA path "
                         f"({reason})")
            return None
        kw = {}
        if has_bias:
            kw = {"b_up": lw["b_up"], "b_down": lw["b_down"]}
        # with apply_norm=False the norm params are unused; ln1_w rides
        # along as a shape-correct dummy
        ln_w = lw["ln2_w"] if apply_norm else lw["ln1_w"]
        ln_b = lw.get("ln2_b") if apply_norm else None
        out = fd.fused_mlp(
            resid[:, 0], y_src[:, 0], ln_w, ln_b,
            lw["w_up"], lw["w_down"], wg, norm=cfg.norm,
            eps=cfg.norm_eps, activation=cfg.activation,
            apply_norm=apply_norm, **kw)
        return out[:, None]

    def _prefill(self, params, ids, prompt_len, cache: KVCache):
        """Process right-padded prompts [B,T]; fill cache[:, :, :T]; return
        (cache, last-token hidden [B,1,D])."""
        import jax
        import jax.numpy as jnp

        from ..ops.flash_attention import flash_attention

        cfg = self._mcfg
        B = ids.shape[0]
        x, (cos, sin), positions = self._embed_at(params, ids, jnp.zeros((B,), jnp.int32))

        def layer_fn(h, lw):
            def attn_fn(q, k, v):
                return flash_attention(q, k, v, causal=True, impl=self.config.attention_impl,
                                       alibi_slopes=self._alibi), (k, v)

            return self._layer_body(lw, h, cos, sin, positions, attn_fn)

        x, (ks, vs) = jax.lax.scan(layer_fn, x, params["layers"])
        k_cache = jax.lax.dynamic_update_slice(cache.k, ks.astype(cache.k.dtype), (0, 0, 0, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache.v, vs.astype(cache.v.dtype), (0, 0, 0, 0, 0))
        x_last = jnp.take_along_axis(x, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1)
        return KVCache(k_cache, v_cache), x_last

    def _ffn(self, lw, y):
        """Dense or MoE FFN on normalized input (mirrors models/transformer.py
        layer_apply; MoE = reference moe_inference.py:159 capability)."""
        import jax

        cfg = self._mcfg
        if cfg.n_experts > 0:
            from ..moe.layer import moe_layer

            expert_params = {n[4:]: lw[n] for n in lw
                             if n.startswith("moe_")
                             and n != "moe_gate" and not n.startswith("moe_shared")}
            # scanned=True: _ffn runs inside the lax.scan over stacked
            # layers, where "auto" takes the capacity path
            # (moe/resolve_moe_impl), same as
            # the training stack_apply call site. Serving (engine_v2) may
            # override impl/capacity_factor from serving.moe and arm a
            # per-layer tap collecting routing counts; both are inert on
            # the training-side engines (attributes absent).
            impl = getattr(self, "_moe_impl_override", None) or cfg.moe_impl
            cf = getattr(self, "_moe_cf_override", None)
            res = moe_layer(lw["moe_gate"], expert_params, y, k=cfg.moe_top_k,
                            capacity_factor=cfg.capacity_factor if cf is None else cf,
                            activation=cfg.activation,
                            impl=impl, normalize_weights=cfg.moe_norm_topk,
                            scanned=True)
            tap = getattr(self, "_moe_tap", None)
            if tap is not None:
                # counts [E] i32 (capacity impl: post-drop; ragged: pre-drop
                # with drop_fraction 0); dropped assignments = drop * S*k,
                # exact because drop_fraction = 1 - kept/(S*k)
                counts = res.metadata["expert_counts"]
                drop = res.metadata.get("drop_fraction", 0.0)
                total = 1
                for d in y.shape[:-1]:
                    total *= int(d)
                tap.append((counts, drop * (total * cfg.moe_top_k)))
            out = res.output
            if cfg.moe_shared_expert_ff > 0:
                shared = (jax.nn.silu(y @ lw["moe_shared_w_gate"])
                          * (y @ lw["moe_shared_w_up"])) @ lw["moe_shared_w_down"]
                gate_s = jax.nn.sigmoid(y @ lw["moe_shared_gate"])
                out = out + gate_s.astype(out.dtype) * shared
            return out
        if cfg.activation == "swiglu":
            return (jax.nn.silu(y @ lw["w_gate"]) * (y @ lw["w_up"])) @ lw["w_down"]
        from ..models.transformer import activation_fn

        act = activation_fn(cfg.activation)
        if not cfg.mlp_bias:
            return act(y @ lw["w_up"]) @ lw["w_down"]
        return act(y @ lw["w_up"] + lw["b_up"].astype(y.dtype)) @ lw["w_down"] + lw["b_down"].astype(y.dtype)

    def _decode_step(self, params, cache: KVCache, tok, pos):
        """One token for every sequence. tok [B], pos [B] = cache fill level.
        Returns (cache, logits [B,V])."""
        import jax
        import jax.numpy as jnp

        B = tok.shape[0]
        x, (cos, sin), _ = self._embed_at(params, tok[:, None], pos)
        barange = jnp.arange(B)

        def layer_fn(h, layer_and_cache):
            lw, ck, cv = layer_and_cache

            def attn_fn(q, k, v):
                ck2 = ck.at[barange, pos].set(k[:, 0].astype(ck.dtype))
                cv2 = cv.at[barange, pos].set(v[:, 0].astype(cv.dtype))
                return decode_attention(q, ck2, cv2, kv_len=pos + 1,
                                        alibi_slopes=self._alibi), (ck2, cv2)

            return self._layer_body(lw, h, cos, sin, pos, attn_fn)

        x, (k_cache, v_cache) = jax.lax.scan(layer_fn, x, (params["layers"], cache.k, cache.v))
        logits = self.model.head(params, x)[:, 0]
        return KVCache(k_cache, v_cache), logits

    # -- public API ----------------------------------------------------

    def forward(self, input_ids):
        """Full-sequence logits (reference inference/engine.py:554)."""
        import numpy as np

        return self._fwd(self.params, np.asarray(input_ids, dtype=np.int32))

    __call__ = forward

    def generate(self, input_ids, prompt_lengths=None, max_new_tokens: Optional[int] = None,
                 temperature: Optional[float] = None, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, eos_token_id: Optional[int] = None,
                 rng=None):
        """Autoregressive generation. input_ids [B, T] right-padded with
        per-seq ``prompt_lengths`` (defaults to full width). Returns int32
        [B, max_new_tokens] (positions after EOS hold pad_token_id).

        Reference guard ``inference/engine.py:583`` delegates to HF
        ``generate``; here the loop itself is on-device.
        """
        import jax
        import jax.numpy as jnp

        cfg = self.config
        ids = np.asarray(input_ids, dtype=np.int32)
        B, T = ids.shape
        if B > cfg.max_batch_size:
            raise ValueError(f"batch {B} exceeds max_batch_size {cfg.max_batch_size} "
                             "(raise it in the inference config)")
        if prompt_lengths is None:
            prompt_lengths = np.full((B,), T, dtype=np.int32)
        prompt_lengths = np.asarray(prompt_lengths, dtype=np.int32)
        max_new = int(max_new_tokens if max_new_tokens is not None else cfg.max_new_tokens)
        temperature = cfg.temperature if temperature is None else float(temperature)
        top_k = cfg.top_k if top_k is None else int(top_k)
        top_p = cfg.top_p if top_p is None else float(top_p)
        eos = cfg.eos_token_id if eos_token_id is None else int(eos_token_id)

        Tpad = min(_bucket(T), cfg.max_seq_len)
        assert T <= Tpad and T + max_new <= cfg.max_seq_len, (
            f"prompt {T} + max_new {max_new} exceeds max_seq_len {cfg.max_seq_len}")
        if Tpad > T:
            ids = np.pad(ids, ((0, 0), (0, Tpad - T)))

        key = (B, Tpad, max_new, temperature == 0.0, top_k, eos)
        fn = self._gen_cache.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(self._generate_impl, max_new=max_new,
                                           greedy=temperature == 0.0, top_k=top_k, eos=eos))
            self._gen_cache[key] = fn
        if rng is None:
            self._rng, rng = jax.random.split(self._rng)
        out = fn(self.params, ids, prompt_lengths, jnp.float32(temperature), jnp.float32(top_p), rng)
        return np.asarray(out)

    def _generate_impl(self, params, ids, prompt_len, temperature, top_p, rng,
                       *, max_new: int, greedy: bool, top_k: int, eos: int):
        import jax
        import jax.numpy as jnp

        cfg = self.config
        mcfg = self._mcfg
        B, Tpad = ids.shape
        S = cfg.max_seq_len
        dtype = cfg.jax_dtype()
        cache = KVCache(
            jnp.zeros((mcfg.n_layers, B, S, mcfg.kv_heads, mcfg.head_dim), dtype),
            jnp.zeros((mcfg.n_layers, B, S, mcfg.kv_heads, mcfg.head_dim), dtype))
        cache, x_last = self._prefill(params, ids, prompt_len, cache)
        logits0 = self.model.head(params, x_last)[:, 0]

        def pick(logits, key):
            if greedy:
                return sampling.greedy(logits)
            return sampling.sample(logits, key, temperature=temperature, top_k=top_k, top_p=top_p)

        rng, k0 = jax.random.split(rng)
        tok0 = pick(logits0, k0)
        done0 = (tok0 == eos) if eos >= 0 else jnp.zeros((B,), bool)

        def step(carry, key):
            cache, tok, pos, done = carry
            new_cache, logits = self._decode_step(params, cache, tok, pos)
            nxt = pick(logits, key)
            nxt = jnp.where(done, cfg.pad_token_id, nxt)
            newly_done = (nxt == eos) if eos >= 0 else jnp.zeros((B,), bool)
            pos = jnp.minimum(pos + 1, S - 1)
            return (new_cache, nxt, pos, done | newly_done), nxt

        keys = jax.random.split(rng, max_new - 1) if max_new > 1 else jnp.zeros((0, 2), jnp.uint32)
        (_, _, _, _), rest = jax.lax.scan(step, (cache, tok0, prompt_len, done0), keys)
        return jnp.concatenate([tok0[None], rest], axis=0).T  # [B, max_new]


def load_serving_weights(ckpt_dir: str, model, tag: Optional[str] = None):
    """Load the MODEL WEIGHTS item of a training checkpoint for serving
    (reference: ``init_inference(checkpoint=...)`` + the mp-sharded
    checkpoint loaders, ``runtime/state_dict_factory.py`` /
    ``module_inject/load_checkpoint.py``). Works for checkpoints written by
    either checkpoint engine; the optimizer bytes are never read.

    Degrades gracefully like the trainer's ``load_checkpoint``: native
    loads are checksum-verified, and when the ``latest`` pointer is torn or
    the tag it names fails an integrity check, serving falls back to the
    newest *complete* earlier tag (one warning) instead of refusing to
    start. An explicit ``tag`` never falls back."""
    import os

    import jax

    from ..checkpoint.engine import (NativeCheckpointEngine, OrbaxCheckpointEngine,
                                     RECOVERABLE_ERRORS, load_with_fallback)

    target = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def load_tag(cand):
        path = os.path.join(ckpt_dir, cand, "model")
        errors, recoverable = [], None
        for eng in (OrbaxCheckpointEngine(), NativeCheckpointEngine()):
            try:
                return eng.load(path, target=target)
            except RECOVERABLE_ERRORS as e:
                recoverable = e
                errors.append(f"{type(eng).__name__}: {type(e).__name__}: {e}")
            except Exception as e:
                errors.append(f"{type(eng).__name__}: {type(e).__name__}: {e}")
        if recoverable is not None:
            # integrity-shaped failure: let load_with_fallback try an
            # earlier complete tag
            raise recoverable
        # structural (wrong model shape etc.): retrying older tags would
        # only bury the real error under 'unusable tag' warnings
        raise ValueError(f"could not load {path} with any checkpoint engine "
                         f"({errors})")

    return load_with_fallback(ckpt_dir, tag, load_tag, what="serving checkpoint")


def init_inference(model=None, params=None, config=None, checkpoint: Optional[str] = None,
                   **kwargs) -> InferenceEngine:
    """Build an InferenceEngine (reference ``deepspeed.init_inference``,
    ``deepspeed/__init__.py:299``). ``config`` is a dict in the reference's
    inference-config format or an InferenceConfig. ``model`` may also be a
    HF checkpoint path or transformers model — the engine-factory dispatch
    of the reference (inference/v2/engine_factory.py:32) via models/hf.py.
    ``checkpoint``: a training-checkpoint dir written by
    ``engine.save_checkpoint`` — its weights item becomes the serving
    params (the reference's checkpoint-loading serving path)."""
    if not isinstance(config, InferenceConfig):
        cfg_dict = dict(config or {})
        cfg_dict.update(kwargs)
        config = InferenceConfig.from_dict(cfg_dict)
    if isinstance(model, str) or (model is not None and hasattr(model, "state_dict")):
        from ..models.hf import from_hf

        model, params = from_hf(model)
    if checkpoint is not None:
        if model is None:
            raise ValueError("init_inference(checkpoint=...) needs the model object")
        params = load_serving_weights(checkpoint, model)
    if params is None:
        raise ValueError("init_inference requires params (the model weights pytree)")
    log_dist(f"init_inference: dtype={config.dtype} tp={config.tensor_parallel} "
             f"max_seq_len={config.max_seq_len}", ranks=[0])
    return InferenceEngine(model, params, config)
