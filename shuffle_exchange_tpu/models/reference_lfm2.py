"""A plain reference for LFM2-8B-A1B (LiquidAI, ``model_type: lfm2_moe``):
forward pass, loss and gradients in straightforward ``jax.numpy``.

``tests/test_lfm2.py`` holds the program (``models/transformer.py`` with mixer
"sconv" beside "attn" with ``qk_norm`` "head", a sigmoid router with a
selection bias, a held share of the experts, a tied head) to this file on
seeded random weights; ``chipbench/reference_lfm2.py`` is its byte-identical
copy below this docstring and decides the cell ``lfm2-train``'s ``correct``.
"""

# Everything below is written from the source's config.json (the catalog's
# row) and the layer equations of ISSUE 41; the leaf names are those of
# transformers' ``Lfm2MoeForCausalLM`` as the builder knows them, with no
# modelling code here to check them against (chipbench/LFM2.md lists what
# that leaves to be assumed). float32 throughout and every matmul at
# jax.default_matmul_precision("highest") (a TPU otherwise multiplies float32
# in bf16 passes); no kernel, no cache, no batching trick, nothing imported
# from shuffle_exchange_tpu. Weights are a flat dict under the source's names,
# each matrix laid out as torch's nn.Linear stores it ([out, in]: y = x @ W.T)
# and the taps as torch's depthwise nn.Conv1d stores them ([D, 1, K]):
#
#   model.embed_tokens.weight                                  [V, D]
#   model.layers.{i}.operator_norm.weight                      [D]
#   model.layers.{i}.ffn_norm.weight                           [D]
#   layers whose layer_types[i] is "conv":
#   model.layers.{i}.conv.in_proj.weight                       [3 D, D]
#   model.layers.{i}.conv.conv.weight                          [D, 1, K]
#   model.layers.{i}.conv.out_proj.weight                      [D, D]
#   the others ("full_attention"):
#   model.layers.{i}.self_attn.q_proj.weight                   [H Dh, D]
#   model.layers.{i}.self_attn.{k,v}_proj.weight               [KV Dh, D]
#   model.layers.{i}.self_attn.{q,k}_layernorm.weight          [Dh]
#   model.layers.{i}.self_attn.out_proj.weight                 [D, H Dh]
#   layers i < num_dense_layers (SwiGLU at intermediate_size):
#   model.layers.{i}.feed_forward.{w1,w3}.weight               [Fd, D]
#   model.layers.{i}.feed_forward.w2.weight                    [D, Fd]
#   the others:
#   model.layers.{i}.feed_forward.gate.weight                  [E, D]
#   model.layers.{i}.feed_forward.expert_bias                  [E]
#   model.layers.{i}.feed_forward.experts.{e}.{w1,w3}.weight   [F, D]
#   model.layers.{i}.feed_forward.experts.{e}.w2.weight        [D, F]
#   model.embedding_norm.weight                                [D]
#   (no lm_head.weight: the head is the embedding, unless ``cfg`` says
#   tie_word_embeddings false; then lm_head.weight [V, D])
#
# ``cfg`` is the source's config.json as a dict (hidden_size,
# num_attention_heads, num_key_value_heads, layer_types, num_dense_layers,
# conv_L_cache, norm_eps, rope_theta, intermediate_size,
# moe_intermediate_size, num_experts, num_experts_per_tok, norm_topk_prob,
# routed_scaling_factor, use_expert_bias, vocab_size) plus ``layers_held``
# (the indices into ``layer_types`` of the layers that exist here, in order;
# without it the first ``num_hidden_layers``; the weights' names carry the
# model's own indices) and, for one expert-parallel rank's share,
# ``num_experts_held`` and ``expert_first`` (the experts [expert_first,
# expert_first + num_experts_held) exist here, the router still scores all
# ``num_experts``).
#
# The equations (D = hidden_size, Dh = D / num_attention_heads, K =
# conv_L_cache):
#   norm       N(x; w) = x * rsqrt(mean(x^2) + eps) * w, a plain gain.
#   block i    h = h + operator_i(N(h; operator_norm));
#              h = h + ffn_i(N(h; ffn_norm)).  Final N (embedding_norm), the
#              head the embedding's transpose, mean token cross-entropy.
#   conv       [B | C | x] = y W_in^T (three blocks of D, in this order);
#              u = B * x; c[t] = sum_j w[:, 0, j] * u[t - (K - 1) + j], u zero
#              before position 0 (torch's Conv1d(D, D, K, groups=D,
#              padding=K-1) cut to T outputs; no bias, no activation), here as
#              K shifted products; out = (C * c) W_out^T.
#   attention  q = y Wq [H x Dh], k = y Wk, v = y Wv [KV x Dh]; q and k normed
#              per HEAD over Dh (N with q_layernorm / k_layernorm, one gain
#              [Dh] each) BEFORE RoPE; RoPE on all Dh dims, rotate-half pairs
#              (i, i + Dh/2), theta rope_theta, unscaled; query head h reads
#              KV head h // (H / KV); causal softmax of q k^T / sqrt(Dh) in
#              float32; out = concat(o) Wo^T.
#   dense ffn  (silu(x W1^T) * x W3^T) W2^T at intermediate_size.
#   sparse ffn s = sigmoid(float32(x) Wr^T) over all E; the k largest of
#              s + expert_bias chosen (the bias selects, is not weighed and
#              gets no gradient); w = s[chosen] / (sum of the chosen s + 1e-6)
#              (norm_topk_prob) times routed_scaling_factor; out = sum over
#              the token's choices THAT ARE HELD HERE of w_k E_{i_k}(x), as a
#              loop over the held experts with masks. No shared expert, no
#              balancing loss.
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the router multiplies in float32 whatever ``dtype`` says;
#   - a rank's share: with ``num_experts_held`` the routed sum runs over the
#     held experts only and what the absent ones would add is left out;
#   - a cut in depth: with ``layers_held`` only those layers run;
#   - the causal softmax is computed a head at a time over the whole [T, T];
#   - ``remat`` wraps each layer, each head and each expert in
#     jax.checkpoint: the same values, computed again in the backward;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the router, the taps' sum and the cross-entropy
#     stay float32.

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x [B, T, H, Dh]: every dim of each head rotated, rotate-half pairs."""
    Dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, Dh]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return x * cos + rotate_half(x) * sin


def linear(x, w):
    return x @ w.astype(x.dtype).T


def layers_held(cfg):
    """The model's own indices of the layers that exist here, in order."""
    return [int(i) for i in cfg.get("layers_held") or range(cfg["num_hidden_layers"])]


def taps_sum(u, taps):
    """The causal depthwise convolution: u [B, T, D], taps [D, 1, K] ->
    c[t] = sum_j taps[:, 0, j] * u[t - (K - 1) + j] with u zero before position
    0, as K shifted products summed in float32."""
    B, T, D = u.shape
    K = taps.shape[-1]
    u32 = u.astype(jnp.float32)
    out = jnp.zeros((B, T, D), jnp.float32)
    for j in range(K):
        back = K - 1 - j                     # tap j reads ``back`` rows before t
        shifted = jnp.pad(u32, ((0, 0), (back, 0), (0, 0)))[:, :T]
        out = out + taps[:, 0, j].astype(jnp.float32)[None, None, :] * shifted
    return out.astype(u.dtype)


def conv_mix(bcx, taps):
    """What lies between the two projections: bcx [B, T, 3 D] -> [B, T, D]."""
    gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)
    return gate_out * taps_sum(gate_in * x, taps)


def short_conv(w, prefix, y):
    """The gated short convolution on the normed input y [B, T, D]."""
    bcx = linear(y, w[prefix + "in_proj.weight"])
    return linear(conv_mix(bcx, w[prefix + "conv.weight"]), w[prefix + "out_proj.weight"])


def head_norms(q, k, w, prefix, eps):
    """q [B, T, H, Dh], k [B, T, KV, Dh] normed per head over Dh."""
    return (rms_norm(q, w[prefix + "q_layernorm.weight"], eps),
            rms_norm(k, w[prefix + "k_layernorm.weight"], eps))


def kv_head(h, H, KV):
    """The KV head that query head h of H reads: consecutive groups of H / KV."""
    return h // (H // KV)


def softmax_rows(scores):
    """Causal scores [.., T, T] float32 -> probabilities, float32."""
    return jax.nn.softmax(scores, axis=-1)


def qk(w, prefix, y, cfg):
    """(q [B, T, H, Dh], k [B, T, KV, Dh]) as the scores read them: projected,
    normed per head, THEN rotated."""
    B, T, D = y.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = D // H
    q = linear(y, w[prefix + "q_proj.weight"]).reshape(B, T, H, Dh)
    k = linear(y, w[prefix + "k_proj.weight"]).reshape(B, T, KV, Dh)
    q, k = head_norms(q, k, w, prefix, cfg["norm_eps"])
    theta = float(cfg["rope_theta"])
    return rope(q, theta), rope(k, theta)


def attention(w, prefix, y, cfg, remat=False):
    B, T, D = y.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = D // H
    q, k = qk(w, prefix, y, cfg)
    v = linear(y, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scale = Dh ** -0.5

    def head(h):
        """whole [T, T] scores of one head."""
        g = kv_head(h, H, KV)
        scores = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, g],
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", softmax_rows(scores).astype(y.dtype), v[:, :, g])

    o = jax.lax.map(jax.checkpoint(head) if remat else head, jnp.arange(H))  # [H, B, T, Dh]
    return linear(jnp.moveaxis(o, 0, 2).reshape(B, T, H * Dh), w[prefix + "out_proj.weight"])


def router_logits(w, prefix, y):
    """y [N, D] -> [N, E] float32: the router multiplies in float32."""
    return y.astype(jnp.float32) @ w[prefix + "gate.weight"].astype(jnp.float32).T


def choose(logits, bias, cfg):
    """The router's logits [N, E] and its selection bias [E] -> (s [N, E]
    float32 scores, chosen [N, k] int32, weight [N, k])."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    select = s
    if cfg.get("use_expert_bias", True):
        select = s + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, chosen = jax.lax.top_k(select, k)
    weight = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weight = weight / (weight.sum(axis=-1, keepdims=True) + 1e-6)
    weight = weight * float(cfg.get("routed_scaling_factor", 1.0))
    return s, chosen.astype(jnp.int32), weight


def route(w, prefix, y, cfg):
    """y [N, D] -> ``choose`` of the layer's logits and bias."""
    return choose(router_logits(w, prefix, y), w[prefix + "expert_bias"], cfg)


def held_range(cfg):
    first = int(cfg.get("expert_first", 0))
    return first, first + int(cfg.get("num_experts_held") or cfg["num_experts"])


def mlp(w, name, y):
    return linear(jax.nn.silu(linear(y, w[name + "w1.weight"]))
                  * linear(y, w[name + "w3.weight"]), w[name + "w2.weight"])


def experts(w, prefix, y, cfg, remat=False):
    """y [N, D] -> (out [N, D], s [N, E], chosen [N, k], weight [N, k]): the
    held experts' part of the routed sum (there is no shared expert)."""
    s, chosen, weight = route(w, prefix, y, cfg)
    first, end = held_range(cfg)
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
    out = jnp.zeros(y.shape, jnp.float32)
    for e in range(first, end):
        # this expert's weight for every token: its normalised score where it
        # is one of the token's k, else 0
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)     # [N]
        out = out + mine[:, None] * one(w, f"{prefix}experts.{e}.", y).astype(jnp.float32)
    return out.astype(y.dtype), s, chosen, weight


def is_dense(i, cfg):
    return i < int(cfg.get("num_dense_layers", 0))


def is_conv(i, cfg):
    return cfg["layer_types"][i] == "conv"


def operator(w, i, y, cfg, remat=False):
    """Layer i's mixer on the normed input."""
    name = f"model.layers.{i}."
    if is_conv(i, cfg):
        return short_conv(w, name + "conv.", y)
    return attention(w, name + "self_attn.", y, cfg, remat)


def layer(w, i, x, cfg, remat=False):
    """Block i (the model's own index): x [B, T, D] -> (x, router scores,
    chosen experts, their weights); the last three are None for a dense
    layer."""
    eps = cfg["norm_eps"]
    B, T, D = x.shape
    name = f"model.layers.{i}."
    y = rms_norm(x, w[name + "operator_norm.weight"], eps)
    h = x + operator(w, i, y, cfg, remat)
    y = rms_norm(h, w[name + "ffn_norm.weight"], eps)
    if is_dense(i, cfg):
        return h + mlp(w, name + "feed_forward.", y), None, None, None
    out, s, chosen, weight = experts(w, name + "feed_forward.", y.reshape(B * T, D), cfg, remat)
    return h + out.reshape(B, T, D), s, chosen, weight


def head_weight(w, cfg):
    """[V, D]: the embedding, unless the configuration unties the head."""
    if cfg.get("tie_word_embeddings", True):
        return w["model.embed_tokens.weight"]
    return w["lm_head.weight"]


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per ROUTED layer the router scores ``s`` [B*T, E], the chosen
    experts ``chosen`` [B*T, k] and their weights ``weight`` [B*T, k]."""
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
        routing = []
        for i in layers_held(cfg):
            block = (jax.checkpoint(layer, static_argnums=(1, 3, 4))
                     if remat else layer)
            x, s, chosen, weight = block(w, i, x, _Static(cfg), remat)
            if chosen is not None:
                routing.append({"s": s, "chosen": chosen, "weight": weight})
        x = rms_norm(x, w["model.embedding_norm.weight"], cfg["norm_eps"])
        logits = linear(x, head_weight(w, cfg)).astype(jnp.float32)
    return logits, routing


class _Static(dict):
    """``cfg`` as a hashable static argument of jax.checkpoint."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def expert_tokens(routing, cfg):
    """[routed layers, E] int32: the token-choices each expert of each routed
    layer received."""
    E = cfg["num_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.int32),
                              axis=(0, 1)) for r in routing])


def expert_weight(routing, cfg):
    """[routed layers, E] float32: the sum of the weights of the token-choices
    each expert of each routed layer received."""
    E = cfg["num_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.float32)
                              * jax.lax.stop_gradient(r["weight"])[..., None],
                              axis=(0, 1)) for r in routing])


def bias_update(bias, tokens, gamma):
    """The aux-free update of one step: bias [routed layers, E], ``tokens``
    [routed layers, E] the token-choices of the step's batch; an expert with
    more than its layer's mean goes down by gamma, one with fewer up."""
    load = tokens.astype(jnp.float32)
    return bias + gamma * jnp.sign(load.mean(axis=-1, keepdims=True) - load)


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, T + 1] -> dict: ``loss`` (the mean token cross-entropy;
    the source has no balancing loss), ``logits`` [B, T, V], ``expert_tokens``
    and ``expert_weight`` [routed layers, E], ``held_rows`` [routed layers]
    (the token-choices that fell on the held experts), ``routing``."""
    logits, routing = forward(w, cfg, batch_ids[:, :-1], dtype, remat)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    tokens = expert_tokens(routing, cfg)
    first, end = held_range(cfg)
    return {"loss": ce, "logits": logits, "expert_tokens": tokens,
            "expert_weight": expert_weight(routing, cfg),
            "held_rows": tokens[:, first:end].sum(axis=1), "routing": routing}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    return loss_parts(w, cfg, batch_ids, dtype, remat)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names (the selection bias is
    a buffer: its entry is zero)."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, E = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["num_experts"]
    Dh, K = D // H, int(cfg.get("conv_L_cache", 3))
    Fd, F = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    first, end = held_range(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "model.embedding_norm.weight": (D,)}
    if not cfg.get("tie_word_embeddings", True):
        shapes["lm_head.weight"] = (V, D)

    def swiglu(name, width):
        return {name + "w1.weight": (width, D), name + "w3.weight": (width, D),
                name + "w2.weight": (D, width)}

    for i in layers_held(cfg):
        name = f"model.layers.{i}."
        a, c, m = name + "self_attn.", name + "conv.", name + "feed_forward."
        shapes.update({name + "operator_norm.weight": (D,), name + "ffn_norm.weight": (D,)})
        if is_conv(i, cfg):
            shapes.update({c + "in_proj.weight": (3 * D, D), c + "conv.weight": (D, 1, K),
                           c + "out_proj.weight": (D, D)})
        else:
            shapes.update({a + "q_proj.weight": (H * Dh, D), a + "k_proj.weight": (KV * Dh, D),
                           a + "v_proj.weight": (KV * Dh, D),
                           a + "q_layernorm.weight": (Dh,), a + "k_layernorm.weight": (Dh,),
                           a + "out_proj.weight": (D, H * Dh)})
        if is_dense(i, cfg):
            shapes.update(swiglu(m, Fd))
            continue
        shapes[m + "gate.weight"] = (E, D)
        shapes[m + "expert_bias"] = (E,)
        for e in range(first, end):
            shapes.update(swiglu(f"{m}experts.{e}.", F))
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for the embedding (and an untied head), 1/sqrt(fan_in) for matrices
    and taps; gains drawn from [0.5, 1.5) and the selection bias from a normal
    of 0.05, wide against the spread of the sigmoid scores of a random router:
    so that leaving one out shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if name.endswith("expert_bias"):
            out[name] = 0.05 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        elif len(shape) == 3:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[-1] ** 0.5)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
