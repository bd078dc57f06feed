"""A plain reference for PowerInfer's SmallThinker-21BA3B-Instruct
(``model_type: smallthinker``): forward pass, loss and gradients in
``jax.numpy``, float32, every matmul at ``jax.default_matmul_precision(
"highest")``; no kernel, no cache, no batching trick, nothing imported from
``shuffle_exchange_tpu``. This file is in the repository TWICE, byte for byte:
``shuffle_exchange_tpu/models/reference_smallthinker.py`` (the program's tests
hold the program to it) and ``chipbench/reference_smallthinker.py`` (the cell
``smallthinker-train``'s ``correct`` is decided by it, and the benchmark
imports nothing of the program to judge it). ``tests/test_smallthinker.py``
holds the two together.
"""

# Written from the source's config.json (the catalog's row), the model's
# report (arXiv:2507.20984) and what I remember of the family's published
# modelling code and its llama.cpp graph (no network here: the configuration
# file's ``assumed`` lists each such item). Weights are a flat dict under the
# family's names, each matrix laid out as torch's nn.Linear stores it
# ([out, in]: y = x @ W.T); H = num_attention_heads, KV = num_key_value_heads,
# Dh = head_dim, D = hidden_size, E = moe_num_primary_experts, F =
# moe_ffn_hidden_size:
#
#   model.embed_tokens.weight                                       [V, D]
#   model.layers.{i}.input_layernorm.weight                         [D]
#   model.layers.{i}.post_attention_layernorm.weight                [D]
#   model.layers.{i}.self_attn.q_proj.weight                        [H Dh, D]
#   model.layers.{i}.self_attn.{k,v}_proj.weight                    [KV Dh, D]
#   model.layers.{i}.self_attn.o_proj.weight                        [D, H Dh]
#   model.layers.{i}.block_sparse_moe.primary_router.weight         [E, D]
#   model.layers.{i}.block_sparse_moe.experts.{e}.{gate,up}.weight  [F, D]
#   model.layers.{i}.block_sparse_moe.experts.{e}.down.weight       [D, F]
#   model.norm.weight                                               [D]
#   lm_head.weight                                                  [V, D]
#
# ``cfg`` is the source's config.json as a dict (hidden_size, head_dim,
# num_attention_heads, num_key_value_heads, num_hidden_layers: the FIRST that
# many entries of ``rope_layout`` and ``sliding_window_layout`` are the layers
# here, sliding_window_size, rope_theta, rms_norm_eps, moe_ffn_hidden_size,
# moe_num_primary_experts, moe_num_active_primary_experts, vocab_size) plus,
# for one expert-parallel rank's share, ``num_experts_held`` and
# ``expert_first`` (the experts [expert_first, expert_first +
# num_experts_held) exist here, the router still scores all E) and
# ``router_aux_loss_coef`` (the balancing loss below; none without the key).
#
# The equations, block i on x [T, D]:
#   r      = x                       the ROUTER's input: the block's input,
#                                    un-normed, taken BEFORE attention
#   y      = N(x; g1)                N(x; g) = x * rsqrt(mean(x^2) + eps) * g
#   q,k,v  = y Wq [H x Dh], y Wk, y Wv [KV x Dh]; query head h reads KV head
#            h // (H / KV) (consecutive groups of 7)
#   rope_layout[i] == 1: q, k rotated, rotate-half pairs (j, j + Dh/2) over
#            all Dh dims, inverse frequencies theta^(-2j/Dh), unscaled;
#            == 0: nothing is rotated and nothing else marks a position
#   a      = softmax(q k^T / sqrt(Dh) + mask) v in float32; key j visible to
#            query t iff 0 <= t - j, and where sliding_window_layout[i] == 1
#            also t - j < sliding_window_size (the window counts the query's
#            own key: transformers' sliding mask)
#   h      = x + a Wo
#   y2     = N(h; g2)
#   s      = r Wr^T [T, E] in float32 (from r, NOT from y2)
#   chosen = the k largest of s; weight = softmax over those k logits
#            (= softmax over all E, the chosen renormalised to sum 1)
#   out    = h + sum over the token's choices THAT ARE HELD HERE of
#            weight * ((relu(y2 Wg_e^T) * (y2 Wu_e^T)) Wd_e^T)
#   then N(.; g_f), the untied head, mean token cross-entropy.
#   balance  ``router_aux_loss_coef`` x HF's load_balancing_loss_func over
#            ALL layers' tokens together: E * sum_e f_e P_e, f_e the mean
#            over tokens and the k choices' slots of "e was chosen", P_e the
#            mean softmax(s)_e over all E.
#
# Departures from the source, each on purpose:
#   - the batch is [B, T + 1] ids: positions [:, :-1] are the input and
#     [:, 1:] the labels, as this repository's trainer feeds a model;
#   - the router multiplies in float32 whatever ``dtype`` says;
#   - a rank's share: with ``num_experts_held`` the routed sum runs over the
#     held experts only and what the absent ones would add is left out;
#   - no second level of experts: the family's description speaks of
#     "primary + secondary" experts, the config has primary keys only and the
#     published 21 B is met without a second level;
#   - the balancing loss: config.json states none; the softmax family's form
#     this repository has, in program and reference alike;
#   - the masked softmax is computed a head and a block of ``QUERY_BLOCK``
#     queries at a time against ALL keys (a dense mask row block), so that
#     16,384 positions fit; the values are those of the whole [T, T] form;
#   - ``remat`` wraps each layer, each head, each query block and each expert
#     in jax.checkpoint: the same values, computed again in the backward;
#   - ``dtype`` other than float32 (bf16) exists only to measure how far a
#     lower precision moves the results: weights and activations are rounded
#     to it; norms, softmaxes, the router and the cross-entropy stay float32.

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
MOE = "block_sparse_moe."


def rms_norm(x, gain, eps):
    """x / rms(x) * gain over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x [B, T, H, Dh]: every dim of each head rotated (rotate-half pairs
    (j, j + Dh / 2)), plain inverse frequencies, no scaling."""
    Dh = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)          # [T, Dh]
    cos = jnp.cos(angles)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[None, :, None, :].astype(x.dtype)
    return x * cos + rotate_half(x) * sin


def linear(x, w):
    return x @ w.astype(x.dtype).T


def rotated(i, cfg):
    """Layer i rotates q and k (rope_layout 1); else it marks no position."""
    return bool(cfg["rope_layout"][i])


def window_of(i, cfg):
    """The keys a query of layer i sees, itself included; 0 = all before it."""
    return int(cfg["sliding_window_size"]) if cfg["sliding_window_layout"][i] else 0


def visible(rows, T, window):
    """[len(rows), T] bool: key j is visible to query i."""
    gap = rows[:, None] - jnp.arange(T)[None, :]
    return (gap >= 0) & ((gap < window) if window else True)


def kv_head(h, H, KV):
    """The KV head that query head h of H reads: consecutive groups of H / KV."""
    return h // (H // KV)


def softmax_rows(scores):
    """Masked scores [.., q, T] float32 -> probabilities, float32."""
    return jax.nn.softmax(scores, axis=-1)


def attention(w, prefix, x, cfg, i, remat=False):
    """The mixer of layer i on its normed input x [B, T, D] -> [B, T, D]."""
    B, T, D = x.shape
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = linear(x, w[prefix + "q_proj.weight"]).reshape(B, T, H, Dh)
    k = linear(x, w[prefix + "k_proj.weight"]).reshape(B, T, KV, Dh)
    v = linear(x, w[prefix + "v_proj.weight"]).reshape(B, T, KV, Dh)
    if rotated(i, cfg):
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    window, scale = window_of(i, cfg), Dh ** -0.5
    block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    starts = jnp.arange(0, T, block)

    def head(h):
        kh, vh = k[:, :, kv_head(h, H, KV)], v[:, :, kv_head(h, H, KV)]

        def rows(start):
            """[block, T] scores of one head's query block against all keys."""
            qb = jax.lax.dynamic_slice_in_dim(q[:, :, h], start, block, axis=1)
            scores = jnp.einsum("bqd,bkd->bqk", qb, kh,
                                preferred_element_type=jnp.float32) * scale
            seen = visible(start + jnp.arange(block), T, window)
            scores = jnp.where(seen[None], scores, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd", softmax_rows(scores).astype(x.dtype), vh)

        o = jax.lax.map(jax.checkpoint(rows) if remat else rows, starts)
        return jnp.moveaxis(o, 0, 1).reshape(B, T, Dh)               # [B, T, Dh]

    o = jax.lax.map(jax.checkpoint(head) if remat else head, jnp.arange(H))
    return linear(jnp.moveaxis(o, 0, 2).reshape(B, T, H * Dh), w[prefix + "o_proj.weight"])


def router_reads(x, y, y2):
    """What the router multiplies, of the block's input x, its norm y and the
    post-attention norm y2: the block's input, un-normed."""
    return x


def router_logits(w, prefix, r):
    """r [N, D] -> [N, E] float32: the router multiplies in float32."""
    return r.astype(jnp.float32) @ w[prefix + "primary_router.weight"].astype(jnp.float32).T


def choose(logits, cfg):
    """The router's logits [N, E] -> (p [N, E] float32, the softmax over all E
    that the balancing loss reads; chosen [N, k] int32; weight [N, k], the
    softmax over the chosen logits)."""
    k = cfg["moe_num_active_primary_experts"]
    logits = logits.astype(jnp.float32)
    top, chosen = jax.lax.top_k(logits, k)
    return jax.nn.softmax(logits, axis=-1), chosen.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def held_range(cfg):
    first = int(cfg.get("expert_first", 0))
    return first, first + int(cfg.get("num_experts_held") or cfg["moe_num_primary_experts"])


def gate_act(x):
    """The nonlinearity on an expert's gate: ReLU (a ReGLU unit)."""
    return jax.nn.relu(x)


def mlp(w, name, y):
    """One expert: (relu(y Wg^T) * (y Wu^T)) Wd^T."""
    return linear(gate_act(linear(y, w[name + "gate.weight"]))
                  * linear(y, w[name + "up.weight"]), w[name + "down.weight"])


def experts(w, prefix, r, y2, cfg, remat=False):
    """r, y2 [N, D] -> (out [N, D], p [N, E], chosen [N, k], weight [N, k]):
    the router reads ``r``, the held experts ``y2``; their part of the sum."""
    p, chosen, weight = choose(router_logits(w, prefix, r), cfg)
    first, end = held_range(cfg)
    one = jax.checkpoint(mlp, static_argnums=(1,)) if remat else mlp
    out = jnp.zeros(y2.shape, jnp.float32)
    for e in range(first, end):
        # this expert's weight for every token: its share of the softmax over
        # the token's chosen logits where it is one of them, else 0
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)     # [N]
        out = out + mine[:, None] * one(w, f"{prefix}experts.{e}.", y2).astype(jnp.float32)
    return out.astype(y2.dtype), p, chosen, weight


def layer(w, i, x, cfg, remat=False):
    """Block i: x [B, T, D] -> (x, router probabilities, chosen experts, their
    weights)."""
    eps = cfg["rms_norm_eps"]
    B, T, D = x.shape
    name = f"model.layers.{i}."
    y = rms_norm(x, w[name + "input_layernorm.weight"], eps)
    h = x + attention(w, name + "self_attn.", y, cfg, i, remat)
    y2 = rms_norm(h, w[name + "post_attention_layernorm.weight"], eps)
    r = router_reads(x, y, y2)
    out, p, chosen, weight = experts(w, name + MOE, r.reshape(B * T, D),
                                     y2.reshape(B * T, D), cfg, remat)
    return h + out.reshape(B, T, D), p, chosen, weight


def forward(w, cfg, input_ids, dtype=jnp.float32, remat=False):
    """input_ids [B, T] -> (logits [B, T, V] float32, routing): ``routing``
    holds per layer the router's softmax ``p`` [B*T, E], the chosen experts
    ``chosen`` [B*T, k] and their weights ``weight`` [B*T, k]."""
    with jax.default_matmul_precision("highest"):
        x = w["model.embed_tokens.weight"].astype(dtype)[input_ids]
        routing = []
        for i in range(cfg["num_hidden_layers"]):
            block = (jax.checkpoint(layer, static_argnums=(1, 3, 4))
                     if remat else layer)
            x, p, chosen, weight = block(w, i, x, _Static(cfg), remat)
            routing.append({"p": p, "chosen": chosen, "weight": weight})
        x = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
        logits = linear(x, w["lm_head.weight"]).astype(jnp.float32)
    return logits, routing


class _Static(dict):
    """``cfg`` as a hashable static argument of jax.checkpoint."""

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


def expert_tokens(routing, cfg):
    """[layers, E] int32: the token-choices each expert of each layer received."""
    E = cfg["moe_num_primary_experts"]
    return jnp.stack([jnp.sum(jax.nn.one_hot(r["chosen"], E, dtype=jnp.int32),
                              axis=(0, 1)) for r in routing])


def balancing_loss(routing, cfg):
    """HF's ``load_balancing_loss_func`` (no attention mask) WITHOUT its
    coefficient: all layers' tokens concatenated, over all E experts."""
    E = cfg["moe_num_primary_experts"]
    p = jnp.concatenate([r["p"] for r in routing], axis=0)               # [L*N, E]
    chosen = jnp.concatenate([r["chosen"] for r in routing], axis=0)     # [L*N, k]
    f = jnp.mean(jax.nn.one_hot(chosen, E, dtype=jnp.float32), axis=0)   # [k, E]
    return E * jnp.sum(f * jnp.mean(p, axis=0)[None, :])


def loss_parts(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """batch_ids [B, T + 1] -> dict: ``loss`` (``ce`` plus
    ``router_aux_loss_coef`` x ``aux`` where ``cfg`` has it), ``ce``, ``aux``,
    ``logits`` [B, T, V], ``expert_tokens`` [layers, E], ``held_rows``
    [layers] (the token-choices that fell on the held experts), ``routing``."""
    logits, routing = forward(w, cfg, batch_ids[:, :-1], dtype, remat)
    labels = batch_ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    aux = balancing_loss(routing, cfg)
    tokens = expert_tokens(routing, cfg)
    first, end = held_range(cfg)
    return {"loss": ce + float(cfg.get("router_aux_loss_coef") or 0.0) * aux,
            "ce": ce, "aux": aux, "logits": logits, "expert_tokens": tokens,
            "held_rows": tokens[:, first:end].sum(axis=1), "routing": routing}


def loss(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    return loss_parts(w, cfg, batch_ids, dtype, remat)["loss"]


def grads(w, cfg, batch_ids, dtype=jnp.float32, remat=False):
    """d loss / d weights, a dict under the same names."""
    return jax.grad(loss)(w, cfg, batch_ids, dtype, remat)


def weight_shapes(cfg):
    """{name: shape} of every tensor the configuration has here."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    E, F = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    first, end = held_range(cfg)
    shapes = {"model.embed_tokens.weight": (V, D), "lm_head.weight": (V, D),
              "model.norm.weight": (D,)}
    for i in range(cfg["num_hidden_layers"]):
        name = f"model.layers.{i}."
        a, m = name + "self_attn.", name + MOE
        shapes.update({name + "input_layernorm.weight": (D,),
                       name + "post_attention_layernorm.weight": (D,),
                       a + "q_proj.weight": (H * Dh, D),
                       a + "k_proj.weight": (KV * Dh, D),
                       a + "v_proj.weight": (KV * Dh, D),
                       a + "o_proj.weight": (D, H * Dh),
                       m + "primary_router.weight": (E, D)})
        for e in range(first, end):
            shapes.update({f"{m}experts.{e}.gate.weight": (F, D),
                           f"{m}experts.{e}.up.weight": (F, D),
                           f"{m}experts.{e}.down.weight": (D, F)})
    return shapes


def init_weights(cfg, seed):
    """Seeded random weights under the source's names (float32): normal,
    0.02 for embedding and head, 1/sqrt(fan_in) for matrices; gains drawn
    from [0.5, 1.5) so that leaving one out shows."""
    shapes = weight_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        if len(shape) == 1:
            out[name] = jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        elif name in ("model.embed_tokens.weight", "lm_head.weight"):
            out[name] = 0.02 * jax.random.normal(key, shape, jnp.float32)
        else:
            out[name] = jax.random.normal(key, shape, jnp.float32) / (shape[1] ** 0.5)
    return out
